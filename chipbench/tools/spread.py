#!/usr/bin/env python3
"""Run a cell several times and report each metric's spread.

    python3 chipbench/tools/spread.py --workload W --seeds 1,2,3,4,5,6 \
        --sets 2 [--trace 0] [--seconds S]

Each run is its own process (``chipbench/run.py``), one after another,
so the chip is free for the next; this process never imports JAX.  A
set runs every seed once; the sets repeat the same seeds.  For each
set and metric it prints the median, the quartiles (Python's
``statistics.quantiles(values, n=4)``) and the spread, the distance
between the quartiles as a share of the median; and every run's
``correct``, checks and the numbers it printed as ``info`` (read, not
compared).  Results go to stdout and to
``chiprun_out/spread.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
OUT = ROOT / "chiprun_out"


def emit(obj) -> None:
    line = json.dumps(obj)
    print(line, flush=True)
    OUT.mkdir(exist_ok=True)
    with open(OUT / "spread.jsonl", "a") as f:
        f.write(line + "\n")


def one_run(workload: str, seed: int, seconds: float, trace: int):
    cmd = [sys.executable, str(HERE / "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    return p.returncode, result, p.stderr[-2000:]


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return {"median": statistics.median(values), "q1": q1, "q3": q3,
            "spread": (q3 - q1) / statistics.median(values)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window length (default: BENCHMARK.json's)")
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    for k in range(args.sets):
        per = {}
        for seed in seeds:
            rc, res, err = one_run(args.workload, seed, seconds, args.trace)
            row = {"set": k, "seed": seed, "rc": rc}
            if res is None:
                row["stderr"] = err
            else:
                row["info"] = [ln for ln in err.splitlines()
                               if ln.startswith("info ")]
                row.update(correct=res["correct"], checks=res["checks"],
                           attempted=res["attempted"], failed=res["failed"],
                           metrics={m: v["value"]
                                    for m, v in res["metrics"].items()},
                           device=res["device"])
                if "breakdown" in res:
                    row["breakdown"] = res["breakdown"]
                for m, v in res["metrics"].items():
                    per.setdefault(m, []).append(v["value"])
            emit(row)
        emit({"set": k, "workload": args.workload, "seconds": seconds,
              "summary": {m: spread(v) for m, v in per.items()
                          if len(v) >= 2}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
