"""One run of one benchmark cell on the chip(s) this process holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python bench/run.py --list

Run from the root of a checkout.  The cell is ``bench/cells/<cell>.json``;
its configuration, traffic module and metric readers are found by name
(``benchlib/harness.py``).  The last line of standard output is the
result as one JSON object; the numbers compared with the reference are
the last lines of standard error.  Where JAX finds no TPU, or fewer
chips than the cell asks for, the run exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=10)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true",
                    help="print the cells found and exit")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(harness.list_cells()))
        return 0
    if not args.workload:
        ap.error("--workload is required")
    job = harness.Job(args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    return harness.run(job)


if __name__ == "__main__":
    sys.exit(main())
