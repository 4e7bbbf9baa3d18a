"""Command line of the chip benchmark.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for. The last line of standard output is the result (JSON); the numbers
that decide ``correct`` are also the last lines of standard error. Exits 3
without printing a result when JAX finds no TPU or too few chips.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here (default: a temporary directory, removed)")
    args = ap.parse_args(argv)

    from chipbench import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace), T0,
                             trace_dir=args.trace_dir)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
