"""The readers of the serving program's own measurements: device idle
time put down to its host spans (``bench.spans``) on a trace written
out by hand, and the per-layer metrics that read its request stamps and
counters on a fake run; each reads nothing where the program wrote
nothing."""
import types
from pathlib import Path

import pytest

from bench import harness, spans

# device operations [0, 2), [5, 6) and [9, 10) us: idle [2, 5) and
# [6, 9).  The server's thread: a run_once [0, 8.8) holding an
# admission [1, 7) whose sync is [3, 4.5), then a harvest [7.5, 8.5)
# (times in picoseconds from 1000 ns)
TEXT = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "bench-server"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 8800000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 1500000 }
    events { metadata_id: 4 offset_ps: 7500000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 2000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "serve.run_once" } }
  event_metadata { key: 2 value { id: 2 name: "serve.admit#uid=3#" } }
  event_metadata { key: 3 value { id: 3 name: "serve.sync" } }
  event_metadata { key: 4 value { id: 4 name: "serve.harvest#block=0#" } }
  event_metadata { key: 5 value { id: 5 name: "np.asarray(jax.Array)" } }
}
"""


def profile(text=TEXT):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def test_idle_time_goes_to_the_innermost_server_span():
    data = profile()
    gaps = spans.idle_gaps(data)
    assert [t for g in gaps for t in g] == pytest.approx(
        [3e-6, 6e-6, 7e-6, 10e-6])
    names = {sp[2] for sp in spans.serve_spans(data)}
    assert names == {"serve.run_once", "serve.admit", "serve.sync",
                     "serve.harvest"}
    by = spans.idle_by_span(gaps, spans.serve_spans(data))
    # gap [2, 5): admission 2-3 and 4.5-5, its sync 3-4.5; gap [6, 9):
    # admission 6-7, run_once 7-7.5 and 8.5-8.8, harvest 7.5-8.5, none
    # 8.8-9
    assert by == pytest.approx({"serve.admit": 2.5e-6,
                                "serve.admit/serve.sync": 1.5e-6,
                                "serve.run_once": 0.8e-6,
                                "serve.harvest": 1e-6, "none": 0.2e-6})


def test_admissions_share_of_idle_time_counts_their_syncs():
    rep = spans.report(profile())
    assert rep["admit_idle_share"] == pytest.approx(100 * 4 / 6)
    assert list(rep["idle_s_by_span"])[0] == "serve.admit"


def test_a_nested_span_of_the_same_start_is_the_inner_one():
    outer, inner = (0.0, 4.0, "serve.run_once"), (0.0, 2.0, "serve.admit")
    by = spans.idle_by_span([(0.0, 3.0)], [outer, inner])
    assert by == pytest.approx({"serve.admit": 2.0, "serve.run_once": 1.0})


def test_two_idle_devices_count_twice():
    by = spans.idle_by_span([(0.0, 1.0), (0.5, 1.0)],
                            [(0.0, 1.0, "serve.harvest")])
    assert by == pytest.approx({"serve.harvest": 1.5})


def test_a_trace_without_server_spans_reads_nothing():
    assert spans.report(profile(TEXT.replace('"serve.', '"other.'))) is None
    recorded = Path(__file__).parent / "data" / "chat-s16.xplane.pb"
    from jax.profiler import ProfileData
    assert spans.report(ProfileData.from_file(str(recorded))) is None
    assert spans.admit_idle_share({}) is None


# ---------------------------------------------------------------------------
# the per-layer readers, on a fake run
# ---------------------------------------------------------------------------

def fake_run(requests=(), before=None, after=None):
    tracked = [types.SimpleNamespace(req=r) for r in requests]
    before, after = before or {}, after or {}
    return types.SimpleNamespace(
        window_requests=lambda: tracked,
        counters=lambda: {k: after[k] - before[k] for k in after
                          if k in before})


def stamped(t_submit, t_admit):
    return types.SimpleNamespace(t_submit=t_submit, t_admit=t_admit)


def read(name, run):
    return harness.load_metric(name)({"run": run, "trace": None})


def test_queue_wait_is_the_median_of_admission_less_submission():
    reqs = [stamped(1.0, 1.5), stamped(2.0, 4.0), stamped(3.0, 3.25)]
    assert read("queue_wait_p50_s.chat", fake_run(reqs)) == 0.5
    # a request never admitted waits forever
    reqs += [stamped(4.0, None), stamped(5.0, None)]
    assert read("queue_wait_p50_s.chat", fake_run(reqs)) == 2.0
    reqs.append(stamped(6.0, None))
    assert read("queue_wait_p50_s.chat", fake_run(reqs)) == float("inf")


def test_queue_wait_reads_nothing_without_stamps():
    unstamped = types.SimpleNamespace(output=[1])
    assert read("queue_wait_p50_s.chat", fake_run([unstamped])) is None
    assert read("queue_wait_p50_s.chat", fake_run()) is None


def test_page_bound_share_reads_the_blocked_counters_over_the_span():
    before = {"queue_blocked_pages_s": 10.0, "queue_blocked_slots_s": 1.0}
    after = {"queue_blocked_pages_s": 13.0, "queue_blocked_slots_s": 2.0}
    got = read("queue_page_bound_share.chat", fake_run(before=before,
                                                       after=after))
    assert got == pytest.approx(75.0)


def test_page_bound_share_reads_nothing_without_a_wait():
    counters = {"queue_blocked_pages_s": 1.0, "queue_blocked_slots_s": 1.0}
    run = fake_run(before=counters, after=counters)
    assert read("queue_page_bound_share.chat", run) is None
    other = {"blocks": 3}
    assert read("queue_page_bound_share.chat",
                fake_run(before=other, after=other)) is None


def test_paged_live_share_reads_the_grid_counters_over_the_span():
    before = {"kv_pages_visited": 100, "kv_pages_live": 40}
    after = {"kv_pages_visited": 300, "kv_pages_live": 90}
    got = read("paged_live_share.chat", fake_run(before=before, after=after))
    assert got == pytest.approx(25.0)


def test_paged_live_share_reads_nothing_without_a_decode_block():
    counters = {"kv_pages_visited": 8, "kv_pages_live": 2}
    assert read("paged_live_share.chat",
                fake_run(before=counters, after=counters)) is None
    assert read("paged_live_share.chat",
                fake_run(before={"blocks": 1}, after={"blocks": 2})) is None


def test_readers_on_a_whole_cpu_run():
    """The tiny cell of ``test_run_cpu`` served for real: every request
    stamped, the grid counted, the wait split by cause."""
    from bench.tests.test_run_cpu import OPEN, run, tiny_config
    out = run(tiny_config("qwen2.5-14b-s16"), OPEN, 2 ** 33 + 11)
    assert out["correct"], out["compared"]
    r = out["run"]
    wait = read("queue_wait_p50_s.chat", r)
    assert 0 <= wait < float("inf")
    live = read("paged_live_share.chat", r)
    assert 0 < live <= 100
    counters = r.counters()
    assert counters["kv_pages_live"] <= counters["kv_pages_visited"]
    share = read("queue_page_bound_share.chat", r)
    assert share is None or 0 <= share <= 100
