"""The trace reduction on a small trace written out by hand, and on the
recorded chip trace where it is present."""
from pathlib import Path

import pytest

from bench import trace

# two overlapping operations and a third after an idle gap, in which
# the host was harvesting; times in picoseconds from 1000 ns
TEXT = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "paged_attention" } }
  event_metadata { key: 3 value { id: 3 name: "jit_loop(7)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "harvest" } }
  event_metadata { key: 2 value { id: 2 name: "serve" } }
}
"""


def summary(window_s=1e-5):
    from jax.profiler import ProfileData
    return trace.reduce(ProfileData.from_text_proto(TEXT), window_s)


def test_busy_time_is_the_union_of_operations():
    s = summary()
    assert s.devices == 1
    assert s.busy_s == pytest.approx(4e-6)
    assert s.op_s == pytest.approx({"fusion.1": 3e-6,
                                    "paged_attention": 2e-6})
    assert s.op_runs == {"fusion.1": 2, "paged_attention": 1}


def test_executables_lose_their_run_id():
    s = summary()
    assert s.module_s == pytest.approx({"jit_loop": 6e-6})
    assert s.time_of(r"(^|_)loop$") == pytest.approx(6e-6)
    assert s.runs_of(r"(^|_)loop$") == 1
    assert s.time_of(r"admit_step") is None
    assert s.time_of("paged_attention", ops=True) == pytest.approx(2e-6)


def test_idle_gap_is_named_by_the_host_work_in_it():
    gaps = summary().breakdown()["idle_gaps"]
    assert gaps == [["harvest", pytest.approx(2e-6)]]


def test_operations_are_short_named_with_their_own_time():
    ops = dict(summary().breakdown()["device_ops"])
    # fusion.1's first run [0, 2) us holds nothing nested; the kernel
    # [1, 3) us starts inside it and is not nested: own times add up
    assert ops == pytest.approx({"fusion.1": 3e-6, "paged_attention": 2e-6})
    assert trace._short("%while.26 = (s32[], bf16[2]{0}) while((s32[] "
                        "%a), condition=%c") == "while.26 while"
    assert trace._short("%closed_call.9 = bf16[32,8]{1,0} custom-call("
                        "s32[32,256] %g)") == "closed_call.9 custom-call"


def test_nested_operations_keep_only_their_own_time():
    own = trace._self_times([(0.0, 10.0, "while"), (1.0, 2.0, "a"),
                             (4.0, 3.0, "b"), (5.0, 1.0, "c")])
    assert own == {"while": 5.0, "a": 2.0, "b": 2.0, "c": 1.0}


def test_a_trace_without_device_operations_is_an_error():
    from jax.profiler import ProfileData
    host_only = TEXT[TEXT.index("planes {\n  id: 2"):]
    with pytest.raises(ValueError):
        trace.reduce(ProfileData.from_text_proto(host_only), 1.0)


RECORDED = Path(__file__).parent / "data" / "chat-s16.xplane.pb"


def test_recorded_chip_trace():
    """0.87 s of chat-s16 on a v5e: one decode block of 8 steps, the
    paged kernel called once per layer and step."""
    from jax.profiler import ProfileData
    from bench import window
    s = trace.reduce(ProfileData.from_file(str(RECORDED)), 0.87)
    assert s.devices == 1
    assert 0.8 < s.busy_s <= s.window_s
    assert s.runs_of(window.DECODE) == 1
    assert s.runs_of(window.KERNEL, ops=True) == 8 * 16
    assert 0.5 * s.busy_s < s.time_of(window.KERNEL, ops=True) < s.busy_s
    ops = s.breakdown()["device_ops"]
    assert len(ops) == trace.TOP and ops[0][0].endswith("custom-call")
