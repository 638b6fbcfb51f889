#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for; a run that finds no TPU, or too few, exits 2 and prints no
result.  Lines before the last are set-up facts; the last line of
standard output is the result, one JSON object.  With ``--trace 0`` its
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  The numbers
that decide ``correct`` are printed beside their limits as the last lines
of standard error and under ``compared``, the result's last key.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(man: dict, cell: str, trace: bool) -> dict[str, str]:
    """name -> unit of the metrics the cell reports in this kind of
    run."""
    group = man["per_layer"] if trace else man["end_to_end"]
    return {m["name"]: m["unit"] for m in group
            if cell in m.get("workloads", [cell])}


def say(msg: str) -> None:
    print(f"set-up: {msg}", flush=True)


def open_chips(chips: int):
    """The devices, or None (with the reason on standard error) where
    JAX finds fewer than ``chips`` TPU chips; then turns on the
    persistent compile cache, caching every program."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {chips} TPU chip(s) needed; found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return None
    from repro.launch.compile_cache import cache_entries, enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    say(f"compile cache {cache}, {cache_entries(cache)} entries")
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)

    man = manifest()
    cells = {w["name"]: w for w in man["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]

    devices = open_chips(cell["chips"])
    if devices is None:
        return 2

    from bench import harness
    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    units = cell_metrics(man, cell["name"], bool(args.trace))
    out = harness.run_cell(
        cell, config, traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), metrics=list(units),
        limits=config["correctness"], log=say, t_process=T_PROCESS,
        trace_dir=args.keep_trace)
    say(f"facts {json.dumps(out['facts'], default=str)}")
    say(f"reference gaps {json.dumps(out['gaps'])}")
    dev = devices[0]
    result = {
        "correct": out["correct"], "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": {k: {"value": v[0] if isinstance(v, tuple) else v,
                        "unit": units[k]}
                    for k, v in out["metrics"].items()},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": out["facts"]["memory_peak_bytes"],
                   **out["device_extra"]},
    }
    if out["breakdown"] is not None:
        result["breakdown"] = out["breakdown"]
    result["compared"] = out["compared"]
    for name, (value, limit) in out["compared"].items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
