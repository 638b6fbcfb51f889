"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only <name>]

Emits ``name,us_per_call,derived`` CSV rows:
  * speedup_analysis — §3.3.3 (70x latency-bound / 15.56x bandwidth-bound)
  * latency_model    — Table 3.1 + Eq 3.1-3.4 / 4.1
  * workloads        — Figure 4.1 TTFT/TPOT/E2E sweep + §4.2 claim checks
  * local_memory     — Table 4.3 local-capacity requirements
  * collectives      — §3.3.2 TAB vs ring on a real device mesh
  * kernels_bench    — Pallas kernels vs oracles
  * roofline         — deliverable (g) per-cell terms (reads dry-run JSONs)
  * serve_bench      — serving hot path: per-token loop vs fused block
                       decode vs block-pool paged KV; also writes the
                       machine-readable ``BENCH_serve.json`` (tokens/s,
                       KV bytes per active token, attention FLOPs/token
                       vs seq len) that CI tracks
"""
from __future__ import annotations

import argparse
import sys
import traceback

MODULES = ("speedup_analysis", "latency_model", "workloads", "local_memory",
           "collectives", "kernels_bench", "roofline", "serve_bench")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=MODULES)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name in MODULES:
        if args.only and name != args.only:
            continue
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row in mod.run():
                print(row)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name},0,FAILED {type(e).__name__}: {str(e)[:160]}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
