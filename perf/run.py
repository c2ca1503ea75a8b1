"""Run one cell of the benchmark once.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip(s).  There is no CPU mode: off a TPU, or
with fewer chips than the cell names, the command exits non-zero and prints
no result.  The last line of standard output is the result object.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from perf.lib.harness import run_cell

    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), PROCESS_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
