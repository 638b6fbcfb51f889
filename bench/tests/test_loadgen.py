"""The traffic generator: seeded, stratified, inside its clips."""
import numpy as np
import pytest

from bench import harness, loadgen

MIXES = ("chat-pow2",)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    traffic = harness.load_json("traffic", mix)
    a, b = loadgen.specs(traffic, 40), loadgen.specs(traffic, 40)
    assert a == b
    ta = loadgen.prompt_tokens(a[3], 152064, 2 ** 33 + 5)
    tb = loadgen.prompt_tokens(b[3], 152064, 2 ** 33 + 5)
    assert np.array_equal(ta, tb)
    assert ta.min() >= 1 and ta.max() < 152064
    assert len(ta) == a[3].prompt_len


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_draw_other_tokens_for_the_same_work(mix):
    traffic = harness.load_json("traffic", mix)
    specs = loadgen.specs(traffic, 40)
    assert [s.prompt_len for s in specs] != sorted(s.prompt_len
                                                   for s in specs)
    ta = loadgen.prompt_tokens(specs[0], 152064, 1)
    tb = loadgen.prompt_tokens(specs[0], 152064, 2)
    assert len(ta) == len(tb) and not np.array_equal(ta, tb)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_inside_their_clips(mix):
    traffic = harness.load_json("traffic", mix)
    pmax, omax = loadgen.max_lengths(traffic)
    for s in loadgen.specs(traffic, 40):
        assert traffic["prompt"]["min"] <= s.prompt_len <= pmax
        assert traffic["output"]["min"] <= s.output_len <= omax
        if traffic["prompt"].get("round_up_pow2"):
            assert s.prompt_len & (s.prompt_len - 1) == 0


def test_open_schedule_spans_warmup_and_window():
    traffic = harness.load_json("traffic", "chat-pow2")
    specs = loadgen.open_schedule(traffic, 40)
    due = [s.due_s for s in specs]
    assert due == sorted(due) and due[0] == 0.0
    assert due[-1] < traffic["warmup_s"] + 40
    rate = len(specs) / (traffic["warmup_s"] + 40)
    assert rate == pytest.approx(traffic["rate_per_s"], rel=0.02)


def test_lognormal_quantiles_have_the_stated_median():
    dist = {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 1,
            "max": 10 ** 6}
    x = loadgen.lengths(dist, 1001)
    assert np.median(x) == 128
    assert list(x) == sorted(x)


@pytest.mark.parametrize("where", ["top", "prompt"])
def test_a_key_the_generator_does_not_read_is_refused(where):
    traffic = harness.load_json("traffic", "chat-pow2")
    if where == "top":
        traffic["shared_prefix_tokens"] = 0
    else:
        traffic["prompt"] = dict(traffic["prompt"], skew=1.0)
    with pytest.raises(ValueError, match="does not read"):
        loadgen.specs(traffic, 40)
