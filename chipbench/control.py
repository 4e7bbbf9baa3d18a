"""Readings that set the limits of ``correct``: the program's own checks and
the control's, on several seeds in one process.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 --steps 1

Per seed: the cell's set-up and ``--steps`` steps of its window, then the
checks as a sound run makes them, and again with the control in the
program's place: the tree rounded to the precision below its own (bfloat16
to float8_e4m3fn, float32 to bfloat16), which breaks the configuration's
guarantee of a bit-identical restore. One JSON line per seed, with each
reading's numbers and the ``correct`` that the harness's own decision
gives it: the control has to come out false. Needs a TPU, like
``chipbench.run``.
"""

import argparse
import gc
import json
import sys
import time

from chipbench import harness


def readings(parts: dict, seed: int, steps: int) -> dict:
    cell = harness.Cell(parts["config"], parts["traffic"], seed, harness.Recorder())
    cell.setup()
    for _ in range(steps):
        cell.step()
    out = {}
    for reading, lower in (("sound", None), ("control", harness.LOWER)):
        checks = cell.checks(lower=lower)
        out[reading] = {k: v["value"] for k, v in checks.items()}
        out[reading]["correct"] = harness.decide(checks)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--steps", type=int, default=1)
    args = ap.parse_args(argv)
    parts = harness.cell_parts(harness.load_bench(), args.workload)
    try:
        harness.check_device(parts["cell"]["chips"])
    except harness.NoChip as e:
        print(f"chipbench.control: {e}", file=sys.stderr)
        return 3
    harness.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = readings(parts, seed, args.steps)
        print(json.dumps({"workload": args.workload, "seed": seed, **r,
                          "seconds": time.perf_counter() - t0}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
