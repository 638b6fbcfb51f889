"""A whole run on the CPU at a tiny size, past the harness's look for a
chip: a sound run is correct, the fp8 control in the program's place is
not, and a served token altered where it is produced is caught.

The tiny model keeps the cell's structure (GQA with QKV bias, paged
KV, the same server, traffic and reference) at widths a test run holds;
its limit is set from its own readings: sound runs read 0 to 0.021 and
the fp8 control 0.079 to 0.118 (open traffic, seeds 1 to 3 and 2**33+7).
"""
import copy
import time

import pytest

from bench import harness

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 512}
LIMIT = {"served_gap": 0.04}
E2E = ["ttft_p50_s", "tpot_p50_ms", "output_tok_s", "hbm_peak_gb",
       "setup_s"]


def tiny_config(name: str, **serving) -> dict:
    cfg = copy.deepcopy(harness.load_json("configs", name))
    cfg["model"].update(TINY)
    cfg["serving"].update(slots=16, max_seq=512, num_pages=300, **serving)
    return cfg


# the warm-up loads the table widths from the median prompt's up, which
# holds when the window always has a request as long as the median: here
# every prompt has 256 tokens (a width of 32 pages)
OPEN = {"name": "tiny-open", "loop": "open", "rate_per_s": 20.0,
        "warmup_s": 1.0,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.5,
                   "min": 200, "max": 256, "round_up_pow2": True},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 2, "max": 32}}
def run(config, traffic, seed, **kw):
    return harness.run_cell({"name": "tiny"}, config, traffic, seed=seed,
                            seconds=2.0, trace=False, metrics=E2E,
                            limits=LIMIT, log=lambda m: None,
                            t_process=time.perf_counter(), **kw)


@pytest.fixture(scope="module")
def sound():
    return run(tiny_config("qwen2.5-14b-s16"), OPEN, 2 ** 33 + 7)


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["compared"]
    assert sound["attempted"] > 10 and sound["failed"] == 0
    assert {"ttft_p50_s", "tpot_p50_ms", "output_tok_s",
            "setup_s"} <= set(sound["metrics"])
    assert list(sound["compared"]) == ["served_gap", "failed_requests",
                                       "stranded_requests",
                                       "programs_built_in_window"]
    assert sound["compared"]["programs_built_in_window"][0] == 0


def test_fp8_control_fails_the_limit():
    """The control's own first choices judged in place of the served
    tokens: the harness's verdict is false, on the same run that reads
    the program within the limit."""
    out = run(tiny_config("qwen2.5-14b-s16"), OPEN, 2 ** 33 + 7,
              control="fp8")
    assert not out["correct"]
    assert out["compared"]["served_gap"] == [out["gaps"]["fp8"],
                                             LIMIT["served_gap"]]
    assert out["gaps"]["fp8"] > LIMIT["served_gap"] >= \
        out["gaps"]["float32"]


def test_altered_token_is_not_correct(monkeypatch):
    """The layer stack in the remote tier, with every decoded token
    moved to its neighbour where it is sampled."""
    from repro.models import transformer
    sample = transformer.sample_tokens_per_slot

    def altered(logits, vocab, temperature, keys):
        return (sample(logits, vocab, temperature, keys) + 1) % vocab

    monkeypatch.setattr(transformer, "sample_tokens_per_slot", altered)
    out = run(tiny_config("qwen2.5-14b-s16", weights_tier="pinned_host"),
              OPEN, 5)
    assert out["attempted"] > 0
    assert not out["correct"]
    assert out["compared"]["served_gap"][0] > LIMIT["served_gap"]


def test_run_without_a_tpu_prints_no_result(capsys):
    from bench import run as entry
    assert entry.main(["--workload", "chat-pow2-s16", "--seed", "1",
                       "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_window_is_the_schedule_not_the_mark():
    """A client thread that marks the window late still counts every
    request due in it, and the tokens it saw from the window's opening."""
    from bench import loadgen
    run = harness.Run.__new__(harness.Run)
    run.tracked = [harness.Tracked(loadgen.Spec(i, 8, 4), due=float(i))
                   for i in range(10)]
    run.window = (2.0, 7.0)
    run.marks = {"w0": {"t": 4.5}, "w1": {"t": 7.0}}
    run.received = [(0.0, 0), (1.5, 10), (2.5, 30), (6.9, 80), (7.5, 95)]
    assert [t.spec.index for t in run.window_requests()] == [2, 3, 4, 5, 6]
    assert run.received_by(2.0) == 10 and run.received_by(7.0) == 80


def test_closed_loop_run_is_correct():
    """Clients that each send their next request when the last one
    completes; the window opens once every client has a first token."""
    closed = {"name": "tiny-closed", "loop": "closed", "clients": 4,
              "prompt": dict(OPEN["prompt"]), "output": dict(OPEN["output"])}
    out = run(tiny_config("qwen2.5-14b-s16"), closed, 11)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 4 and out["failed"] == 0
