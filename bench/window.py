"""What the window held, for the per-layer readers: the work done in
it, counted by the client from its own requests, and the names the
trace gives the program's executables and kernel.

A decoded token is the ``j``-th output token (``j >= 2``; the first
comes from the prefill) of a request whose prompt has ``p`` positions:
its step attends ``p + j - 1`` keys, of which ``p + j - 2`` are pooled
and one is the current token's own.
"""
from __future__ import annotations

import numpy as np

from bench import flops, peaks

DECODE = r"(^|_)loop$"          # the fused decode block's executable
ADMIT = r"admit_step"           # admission: prefill of one request
# the Pallas paged-attention kernel: a Mosaic custom call whose first
# two operands are the scalar-prefetched page table and lengths
KERNEL = (r"custom-call\(s32\[\d+,\d+\]\S* \S+, s32\[\d+\]\S* \S+, .*"
          r'custom_call_target="tpu_custom_call"')


def decoded(run) -> list[tuple[int, int]]:
    """(prompt positions, j) of every token decoded in the profiled
    span."""
    out0, out1 = run.marks["p0"]["out"], run.marks["p1"]["out"]
    toks = []
    for t in run.tracked:
        a, b = out0.get(t.spec.index, 0), out1.get(t.spec.index, 0)
        p = t.spec.prompt_len
        toks += [(p, j) for j in range(max(a, 1) + 1, b + 1)]
    return toks


def prefilled(run) -> list[int]:
    """Prompt lengths of the requests whose first token came in the
    profiled span."""
    t0, t1 = run.marks["p0"]["t"], run.marks["p1"]["t"]
    return [t.spec.prompt_len for t in run.tracked
            if t.first is not None and t0 <= t.first < t1]


def model_flops(run) -> float:
    s = run.sizes
    return (sum(flops.prefill_flops(s, p) for p in prefilled(run))
            + sum(flops.decode_flops(s, p + j - 1) for p, j in decoded(run)))


def mfu(ctx) -> float | None:
    run, tr = ctx["run"], ctx["trace"]
    work = model_flops(run)
    if not work:
        return None
    peak = peaks.peaks(run.dev.device_kind)["bf16_flops"]
    return 100.0 * work / (tr.window_s * tr.devices * peak)


def idle_share(ctx) -> float:
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def decode_step_ms(ctx) -> float | None:
    run, tr = ctx["run"], ctx["trace"]
    t = tr.time_of(DECODE)
    steps = tr.runs_of(DECODE) * run.config["serving"]["block_size"]
    return None if t is None or not steps else 1e3 * t / steps


def percentile(values, q) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None
