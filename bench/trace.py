"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to device busy time, time per executable
and per operation, and the idle gaps with what the host was doing in
each.

Device planes are those named ``/device:TPU:<n>``; on each, the line
``XLA Modules`` holds one event per executable run and ``XLA Ops`` one
event per operation.  Busy time is the union of the operation intervals.
A trace without device operations is an error: a reader must never turn
"nothing found" into a zero.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
TOP = 10


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _base(name: str) -> str:
    """An executable's name without its run id suffix."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def _short(name: str) -> str:
    """An operation's name as the trace gives it is its whole HLO
    instruction (``%fusion.3 = bf16[...] fusion(...), ...``); keep the
    instruction's name and opcode."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    op = re.search(r"\s([a-z][a-z0-9_-]*)\(", rest)
    return f"{head.lstrip('%')} {op.group(1)}" if op else head.lstrip("%")


def _self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Each operation's own time: its duration less that of the
    operations nested in it (a loop's body runs inside the loop's
    event on the same line)."""
    own: dict[str, float] = {}
    stack: list[list] = []              # [end, name]
    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack and start + dur <= stack[-1][0]:
            parent = stack[-1][1]
            own[parent] = own.get(parent, 0.0) - dur
        own[name] = own.get(name, 0.0) + dur
        stack.append([start + dur, name])
    return own


@dataclasses.dataclass
class Summary:
    window_s: float                    # host-clock length of the trace
    busy_s: float                      # mean over devices
    devices: int
    module_s: dict[str, float]         # executable -> device seconds
    module_runs: dict[str, int]
    op_s: dict[str, float]             # operation (whole text) -> seconds
    op_runs: dict[str, int]
    op_self_s: dict[str, float]        # short name -> own seconds
    gaps: list[tuple[str, float]]      # longest idle gaps, by host work

    def time_of(self, pattern: str, *, ops: bool = False) -> float | None:
        """Device seconds of the executables (or operations) whose name
        matches ``pattern``; None where none ran."""
        table = self.op_s if ops else self.module_s
        hits = [v for k, v in table.items() if re.search(pattern, k)]
        return sum(hits) if hits else None

    def runs_of(self, pattern: str, *, ops: bool = False) -> int:
        table = self.op_runs if ops else self.module_runs
        return sum(v for k, v in table.items() if re.search(pattern, k))

    def breakdown(self) -> dict:
        top = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def _host_label(events: list[tuple[float, float, str]], s: float,
                e: float) -> str:
    """What the host was doing in [s, e): the host event that overlaps
    the gap most, the shorter one on a tie."""
    best, key = "no host event", (0.0, 0.0)
    for hs, he, name in events:
        ov = min(he, e) - max(hs, s)
        if ov > 0 and (ov, -(he - hs)) > key:
            best, key = name, (ov, -(he - hs))
    return best


def reduce(data, window_s: float) -> Summary:
    """Reduce a ``ProfileData`` to a :class:`Summary`."""
    devices = [p for p in data.planes if DEVICE_PLANE.match(p.name)]
    module_s: dict[str, float] = {}
    module_runs: dict[str, int] = {}
    op_s: dict[str, float] = {}
    op_runs: dict[str, int] = {}
    op_self: dict[str, float] = {}
    busy_total = 0.0
    gaps: list[tuple[float, float]] = []
    span = 0.0
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                for ev in line.events:
                    k = _base(ev.name)
                    module_s[k] = module_s.get(k, 0.0) + ev.duration_ns * 1e-9
                    module_runs[k] = module_runs.get(k, 0) + 1
            elif line.name == OPS_LINE:
                events = []
                for ev in line.events:
                    start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                    op_s[ev.name] = op_s.get(ev.name, 0.0) + dur
                    op_runs[ev.name] = op_runs.get(ev.name, 0) + 1
                    intervals.append((start, start + dur))
                    events.append((start, dur, _short(ev.name)))
                for k, v in _self_times(events).items():
                    op_self[k] = op_self.get(k, 0.0) + v
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged)
        if merged:
            span = max(span, merged[-1][1] - merged[0][0])
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    if not op_s:
        raise ValueError("the trace holds no device operation: no "
                         f"'{OPS_LINE}' events on any /device:TPU plane")
    host = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             ev.name)
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events if ev.duration_ns > 0]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_host_label(host, s, e), e - s) for s, e in gaps[:TOP]]
    # the device clock is not the host's: a window the device overran
    # (operations issued before the trace stopped) is the device's span
    return Summary(window_s=max(window_s, span),
                   busy_s=busy_total / len(devices),
                   devices=len(devices), module_s=module_s,
                   module_runs=module_runs, op_s=op_s, op_runs=op_runs,
                   op_self_s=op_self, gaps=named)


def newest_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir: Path, window_s: float) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(newest_xplane(trace_dir))),
                  window_s)
