"""§3.3.3 speed-up analysis: exact paper numbers + model properties."""
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import analysis, hw, latency


def test_paper_headline_numbers():
    h = analysis.paper_headline_numbers(8)
    assert h["enabler1_latency_bound"] == 14.0
    assert h["enabler1_bandwidth_bound"] == 1.75
    assert h["enabler2_bandwidth_bound"] == pytest.approx(8.89, abs=0.01)
    assert h["overall_latency_bound"] == 70.0
    assert h["overall_bandwidth_bound"] == pytest.approx(15.56, abs=0.01)


def test_exact_component_ratios():
    r = analysis.speedup_report(8)
    assert r.enabler2_latency_bound_read == pytest.approx(1000 / 220)
    assert r.enabler2_latency_bound_write == pytest.approx(500 / 90)
    assert r.enabler1_latency_bound == 14


@given(n=st.integers(min_value=2, max_value=64))
@settings(max_examples=30, deadline=None)
def test_enabler1_structure(n):
    r = analysis.speedup_report(n)
    # ring does 2(N-1) transfers; FH always 1
    assert r.enabler1_latency_bound == 2 * (n - 1)
    # bandwidth-bound data ratio 2(N-1)/N in [1, 2)
    assert 1.0 <= r.enabler1_bandwidth_bound < 2.0
    # overall speedups grow monotonically with N
    r2 = analysis.speedup_report(n + 1)
    assert r2.overall_latency_bound > r.overall_latency_bound


def test_table_3_1_totals():
    t = latency.table_3_1_totals_ns()
    assert t["read"] == 220
    assert t["write"] == 90
    assert t["atomic_completion"] == 40


@given(size=st.floats(min_value=1.0, max_value=1e12))
@settings(max_examples=40, deadline=None)
def test_latency_equations(size):
    bw = 4.0e12
    r = latency.fh_read_latency_s(size, bw)
    w = latency.fh_write_latency_s(size, bw)
    wa = latency.fh_write_accumulate_latency_s(size, bw)
    assert r == pytest.approx(220e-9 + size / bw)
    assert w == pytest.approx(90e-9 + size / bw)
    assert wa == w
    assert latency.fh_completion_notification_latency_s() == 40e-9


@given(size=st.floats(min_value=1.0, max_value=1e11),
       n=st.integers(min_value=2, max_value=16))
@settings(max_examples=40, deadline=None)
def test_fh_collectives_beat_ring(size, n):
    """With the paper's constants, FengHuang allreduce is faster than the
    NVLink ring at every size and GPU count."""
    fh = latency.fh_allreduce_time_s(size, n)
    ring = latency.nvlink_ring_allreduce_time_s(size, n)
    assert fh < ring


@given(a=st.floats(min_value=1.0, max_value=1e9))
@settings(max_examples=30, deadline=None)
def test_efficiency_curve_monotone(a):
    link = latency.LinkModel(0.0, 4e12)
    assert link.efficiency(a) <= link.efficiency(a * 2) + 1e-12
    assert latency.LinkModel(0.0, 4e12).transfer_time(a) < \
        latency.LinkModel(0.0, 4e12).transfer_time(a * 2)


def test_collective_dispatch_covers_all():
    for fabric in ("fh", "nvlink"):
        for kind in latency.COLLECTIVES:
            t = latency.collective_time_s(kind, fabric, 1 << 20, 8)
            assert t > 0
    with pytest.raises(ValueError):
        latency.collective_time_s("bogus", "fh", 1, 8)
