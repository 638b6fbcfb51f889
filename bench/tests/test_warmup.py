"""The warm-up reaches every decode program the window can use: the
plan's bursts land in their buckets, and on a tiny model with the
cell's serving geometry each (table width, bucket) pair runs."""
import copy

import numpy as np
import pytest

from bench import harness, loadgen

CELL = harness.load_json("configs", "qwen2.5-14b-s16")
SRV = CELL["serving"]
TRAFFIC = harness.load_json("traffic", "chat-pow2")
SPECS = loadgen.specs(TRAFFIC, 51)
LONGEST = max(s.prompt_len for s in SPECS)
MEDIAN = int(np.median([s.prompt_len for s in SPECS]))


def plan():
    return harness.table_plan(
        LONGEST, MEDIAN, page=SRV["page_size"], slots=SRV["slots"],
        pages=SRV["num_pages"] - 1, max_seq=SRV["max_seq"],
        block=SRV["block_size"])


def test_widths_run_from_the_median_prompt_to_max_seq():
    assert [p["width"] for p in plan()] == [64, 128, 256]


@pytest.mark.parametrize("width", [64, 128, 256])
def test_each_burst_stays_inside_its_bucket(width):
    phase = {p["width"]: p for p in plan()}[width]
    page = SRV["page_size"]
    assert width // 2 < -(-(phase["holder"] + 1) // page)
    assert -(-(phase["holder"] + phase["holder_new"]) // page) <= width
    prev, buckets = 0, {4}
    for step in phase["steps"][2:]:
        if step["requests"] == 0:
            prev = 0
            continue
        c = step["bucket"]
        assert c // 2 < step["entries"] - prev
        assert step["entries"] + prev + 1 <= c
        assert step["requests"] < SRV["slots"]
        assert step["prompt"] <= LONGEST
        assert step["prompt"] & (step["prompt"] - 1) == 0
        prev = step["entries"]
        buckets.add(c)
    most = 1024 if width == 64 else 2048
    assert buckets == {4 << i for i in range(most.bit_length() - 2)}


def test_tiny_model_runs_every_planned_program(monkeypatch):
    from repro.runtime import serve
    cfg = copy.deepcopy(CELL)
    cfg["model"].update({"hidden_size": 32, "intermediate_size": 64,
                         "num_attention_heads": 2, "num_key_value_heads": 1,
                         "head_dim": 16, "num_hidden_layers": 1,
                         "vocab_size": 256})
    seen = set()
    delta = serve.BatchedServer._table_delta

    def spy(self):
        d = delta(self)
        seen.add((self._table_w, None if d is None else d[0].shape[0]))
        return d

    monkeypatch.setattr(serve.BatchedServer, "_table_delta", spy)
    run = harness.Run({"name": "tiny"}, cfg, TRAFFIC, seed=3, seconds=51,
                      trace=False, log=lambda m: None)
    run.build()
    run.warm_tables()
    want = set()
    for p in plan():
        top = 1024 if p["width"] == 64 else 2048
        want |= {(p["width"], None)} | {(p["width"], 4 << i)
                                         for i in range(top.bit_length() - 2)}
    assert want <= seen


def test_adapter_refuses_a_serving_key_it_does_not_read():
    from bench.adapters import dense_gqa
    cfg = copy.deepcopy(CELL)
    cfg["serving"]["kv_dtype"] = "int8"
    with pytest.raises(ValueError, match="does not read"):
        dense_gqa.server(None, None, cfg, 1)
