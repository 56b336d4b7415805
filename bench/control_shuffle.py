"""Readings of a shuffle cell's control: the plain reference of
``benchlib/bigreference.py`` put in the program's place with its
density computed one precision below the configuration's float32, in
bfloat16, and compared as a run compares.

    python bench/control_shuffle.py --workload <cell> --seeds 1 2 3

Prints one line of compared numbers per seed, and the seconds the table
and the reference took.  The control must come out as not correct, by
``density_rel_gap`` alone.  It needs no chip and is not part of a
benchmark run.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import ml_dtypes  # noqa: E402

from benchlib import bigratings, bigreference, compare, harness, tables  # noqa: E402,F401


def control_numbers(cfg: dict, seed: int) -> tuple:
    """(compared numbers, {phase: seconds}) of the control on ``seed``."""
    t0 = time.perf_counter()
    _, tuples, values = tables.make_table(cfg["table"], seed)
    t1 = time.perf_counter()
    want = bigreference.mine_config(cfg["mine"], tuples, values)
    t2 = time.perf_counter()
    low = want["density"].astype(ml_dtypes.bfloat16)
    numbers = bigreference.numbers(bigreference.as_result(want, low), want)
    # the control answers alike every time and drops no record
    numbers.update(repeat_mismatch=0, overflow=0)
    return numbers, {"table_s": t1 - t0, "reference_s": t2 - t1,
                     "compare_s": time.perf_counter() - t2}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = {w["name"]: w for w in harness.load_benchmark()["workloads"]}
    cfg = harness.load_json("configs", spec[args.workload]["config"])
    limits = harness.load_json("cells", args.workload)["limits"]
    for seed in args.seeds:
        numbers, seconds = control_numbers(cfg, seed)
        ok, table = compare.judge(numbers, limits)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": ok, "checks": table,
                          "seconds": seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
