#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload chat-pow2-s16 --seeds 1,2,3 --seconds 15

For each seed, one run of the cell at its own size and load (a shorter
window), in one process, judged with the control in the program's place:
the reference computed with every matmul operand rounded to float8_e4m3,
whose first choice at each served position is the token judged.  Each
line gives the control's verdict (``correct``, which has to come out
false) and both readings: the program's served gap (the widest gap by
which a served token's float32 reference logit lies below the
reference's best) and the control's.  The limit lies between the
program's largest reading and the control's smallest.

The benchmark's own runs do not run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.run import manifest, open_chips, say
    cell = {w["name"]: w for w in manifest()["workloads"]}[args.workload]
    if open_chips(cell["chips"]) is None:
        return 2
    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.run_cell(
            cell, config, traffic, seed=seed, seconds=args.seconds,
            trace=False, metrics=[], limits=config["correctness"], log=say,
            t_process=t, control="fp8")
        print(json.dumps({
            "workload": cell["name"], "seed": seed,
            "correct": out["correct"], "compared": out["compared"],
            "served_gap": out["gaps"].get("float32"),
            "fp8_gap": out["gaps"].get("fp8"),
            "attempted": out["attempted"], "failed": out["failed"],
            "reference_tokens": out["facts"]["reference_tokens"],
            "reference_s": out["facts"]["reference_s"],
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
