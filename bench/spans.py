"""Device idle time put down to the serving program's own host spans.

``repro.runtime.serve.BatchedServer`` writes its spans through
``jax.profiler.TraceAnnotation`` into the same ``.xplane.pb`` as the
device planes: ``serve.run_once`` (a whole call), ``serve.admit`` (one
request's admission, with its ``uid``), ``serve.dispatch`` and
``serve.harvest`` (one decode block, with its index) and ``serve.sync``
(the blocking copy to the host inside an admission or a harvest).

Each idle gap between the device's operations (as :mod:`bench.trace`
merges them) is split by the innermost span open over each part of it
(the server's spans nest on its thread); ``serve.sync`` is named with
its parent (``serve.admit/serve.sync``), and a part under no span is
``none``.

    python3 -m bench.spans <trace dir>

prints this for the newest trace under the directory (``bench/run.py
--keep-trace``), with the share of the idle time inside admissions.
"""
from __future__ import annotations

import json
import sys

from bench import trace

PREFIX = "serve."
SYNC = "serve.sync"
ADMIT = "serve.admit"
NONE = "none"


def span_name(name: str) -> str:
    """A span's name without the ``#key=value#`` suffix under which
    ``TraceAnnotation`` can carry its keyword arguments."""
    return name.split("#", 1)[0]


def serve_spans(data) -> list[tuple[float, float, str]]:
    """(start, end, name) in seconds of the host events named
    ``serve.*``."""
    return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             span_name(ev.name))
            for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name.startswith(PREFIX)]


def idle_gaps(data) -> list[tuple[float, float]]:
    """The idle intervals between each device's merged operations."""
    gaps = []
    for plane in data.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        ops = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
               for line in plane.lines if line.name == trace.OPS_LINE
               for ev in line.events]
        merged = trace._union(ops)
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return gaps


def _label(open_spans: list[tuple[float, float, str]]) -> str:
    """The innermost open span (latest start, the shorter on a tie);
    a sync is named with the span it syncs for."""
    if not open_spans:
        return NONE
    inner = sorted(open_spans, key=lambda sp: (sp[0], -sp[1]))
    if inner[-1][2] == SYNC and len(inner) > 1:
        return f"{inner[-2][2]}/{SYNC}"
    return inner[-1][2]


def idle_by_span(gaps: list[tuple[float, float]],
                 spans: list[tuple[float, float, str]]) -> dict[str, float]:
    """Idle seconds under each innermost span, and under none."""
    events = ([(s, 1, None) for s, _ in gaps]
              + [(e, -1, None) for _, e in gaps]
              + [(sp[0], 1, sp) for sp in spans]
              + [(sp[1], -1, sp) for sp in spans])
    events.sort(key=lambda ev: ev[0])
    out: dict[str, float] = {}
    idle, open_spans, prev = 0, [], None
    for t, step, sp in events:
        if idle and t > prev:
            label = _label(open_spans)
            out[label] = out.get(label, 0.0) + idle * (t - prev)
        prev = t
        if sp is None:
            idle += step
        elif step > 0:
            open_spans.append(sp)
        else:
            open_spans.remove(sp)
    return out


def admit_idle_share(by_span: dict[str, float]) -> float | None:
    """Percent of the idle time spent inside admissions (their syncs
    included); None where the device never idled."""
    total = sum(by_span.values())
    if total <= 0:
        return None
    admit = sum(v for k, v in by_span.items()
                if k == ADMIT or k.startswith(ADMIT + "/"))
    return 100.0 * admit / total


def report(data) -> dict | None:
    """Idle seconds by span, largest first, and the admissions' share;
    None for a trace without the server's spans."""
    spans = serve_spans(data)
    if not spans:
        return None
    by = idle_by_span(idle_gaps(data), spans)
    return {"idle_s_by_span": dict(sorted(by.items(), key=lambda kv: -kv[1])),
            "admit_idle_share": admit_idle_share(by)}


def main(argv: list[str]) -> int:
    from jax.profiler import ProfileData
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = trace.newest_xplane(argv[0])
    print(json.dumps(report(ProfileData.from_file(str(path)))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
