#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate at which the
backlog does not grow over a window.

    python3 bench/sweep.py --workload chat-pow2-s16 --seed 5 \
        --rates 1.0,1.2,1.4,1.6 --seconds 30 --warmup 10

One process builds and warms the cell once, then drives its traffic at
each rate in turn (ascending), each with its own warm-up and window,
and prints one JSON line per rate: the backlog (requests sent and not
yet given their first token) at the window's middle and end, its growth
per second, and the median time to first token in each half of the
window.  A rate overloads when the backlog grows by more than 5% of its
arrivals or the second half's median wait is more than twice the
first's; the sweep stops after two such rates.  The knee is written
into the traffic file by hand, with the cell's rate at 0.8 times it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def backlog(tracked, t: float) -> int:
    return sum(1 for x in tracked if x.req is not None and x.sent <= t
               and (x.first is None or x.first > t))


def median(v):
    import numpy as np
    return float(np.median(v)) if v else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--warmup", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench import harness, loadgen
    from bench.run import manifest, open_chips, say
    cell = {w["name"]: w for w in manifest()["workloads"]}[args.workload]
    if open_chips(cell["chips"]) is None:
        return 2
    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    if traffic["loop"] != "open":
        print("sweep: only an open loop has a rate", file=sys.stderr)
        return 2
    base = harness.Run(cell, config, traffic, seed=args.seed,
                       seconds=args.seconds, trace=False, log=say)
    base.build()
    base.warm_tables()
    base.warm_shapes()
    over = 0
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(traffic, rate_per_s=rate, warmup_s=args.warmup)
        run = harness.Run(cell, config, tr, seed=args.seed + i + 1,
                          seconds=args.seconds, trace=False, log=say)
        run.watch, run.model, run.server = base.watch, base.model, base.server
        run.dev, run.vocab = base.dev, base.vocab
        run.specs = loadgen.specs(tr, args.seconds)
        run.drive()
        t0, t1 = run.window
        mid = (t0 + t1) / 2
        b_mid, b_end = backlog(run.tracked, mid), backlog(run.tracked, t1)
        win = run.window_requests()
        wait = lambda lo, hi: [x.first - x.due for x in win  # noqa: E731
                               if lo <= x.due < hi and x.first is not None]
        first_half, second_half = median(wait(t0, mid)), median(wait(mid, t1))
        growth = (b_end - b_mid) / (t1 - mid)
        overloaded = (growth > 0.05 * rate or second_half is None
                      or (first_half and second_half > 2 * first_half))
        over += bool(overloaded)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(win),
            "backlog_mid": b_mid, "backlog_end": b_end,
            "growth_per_s": growth, "ttft_median_first_half_s": first_half,
            "ttft_median_second_half_s": second_half,
            "output_tok_s": (run.received_by(t1)
                             - run.received_by(t0)) / (t1 - t0),
            "compiles_in_window": run.facts["compiles_in_window"],
            "overloaded": bool(overloaded)}), flush=True)
        if over >= 2:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
