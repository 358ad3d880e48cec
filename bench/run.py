"""Benchmark entry point, run from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON line as the last line of stdout (see ``bench/harness.py``);
exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program is missing.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness, spec
    try:
        harness.run(args, T_START, spec.Spec())
    except harness.NoChip as e:
        print(f"[bench] refused: {e}", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    return 0


if __name__ == "__main__":
    sys.exit(main())
