#!/usr/bin/env python3
"""Find a cell's knee: the population at which the server starts to slow
its users down.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --users 16 24 32 ...

One process runs the cell once per population size (no output check),
and prints per size the turns per second against what an instant server
would give (users over the mean cycle, ``generator.cycle_s``) and the
TTFP tails. The knee is the largest size whose turns per second is at
least 95% of that. Run once, when a cell is defined; the cell then runs
at four fifths of it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--users", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    rows = []
    for n in args.users:
        out = run.execute(args.workload, args.seed, args.seconds, False,
                          users=n, check_outputs=False)
        r = out.pop("run")
        m = {k: v["value"] for k, v in out["metrics"].items()}
        ideal = n / r.mean_cycle_s
        row = {"users": n, "turns_per_s": m.get("turns_per_s"),
               "ideal_turns_per_s": ideal,
               "share_of_ideal": (m.get("turns_per_s") or 0.0) / ideal,
               "ttfp_p50_s": m.get("ttfp_p50_s"),
               "ttfp_p90_s": m.get("ttfp_p90_s"),
               "attempted": out["attempted"], "failed": out["failed"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del out, r
        gc.collect()
    knee = max((r["users"] for r in rows if r["share_of_ideal"] >= 0.95),
               default=None)
    print(json.dumps({"knee": knee, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
