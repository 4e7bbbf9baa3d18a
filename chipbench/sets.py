"""Run a cell once per seed, each run its own process, and report the
spread of each metric: how the bounds in ``BENCHMARK.json`` are measured.

    python3 -m chipbench.sets --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--trace 0] [--sets 2] --out <file>.jsonl

This process never imports JAX, so each child has the chip to itself. Each
run's result line (with its seed, set and exit code) is appended to
``--out``; the end of its standard error goes beside it. The spread of a
metric is the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) over its median.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time


def spread(values: list[float]) -> float:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.sets")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="10")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = args.seeds.split(",")
    rows = []
    for k in range(args.sets):
        for seed in seeds:
            cmd = [sys.executable, "-m", "chipbench.run", "--workload", args.workload,
                   "--seed", seed, "--seconds", args.seconds, "--trace", args.trace]
            t0 = time.perf_counter()
            p = subprocess.run(cmd, capture_output=True, text=True)
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1])
            except (IndexError, json.JSONDecodeError):
                result = None
            row = {"workload": args.workload, "set": k, "seed": int(seed), "rc": p.returncode,
                   "wall_s": time.perf_counter() - t0, "result": result,
                   "stderr_tail": p.stderr[-3000:]}
            rows.append(row)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            brief = result and {m: v["value"] for m, v in result["metrics"].items()}
            print(json.dumps({"set": k, "seed": seed, "rc": p.returncode,
                              "correct": result and result["correct"], "metrics": brief}),
                  flush=True)
    for k in range(args.sets):
        ok = [r["result"] for r in rows if r["set"] == k and r["result"]]
        names = sorted({m for r in ok for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
            if len(vals) >= 2:
                print(json.dumps({"set": k, "metric": m, "median": statistics.median(vals),
                                  "spread": spread(vals), "n": len(vals)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
