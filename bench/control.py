"""Readings of a cell's control: the plain reference put in the
program's place with its density computed one precision below the
configuration's float32, in bfloat16, and compared as a run compares.

    python bench/control.py --workload <cell> --seeds 1 2 3

Prints one line of compared numbers per seed.  The control must come out
as not correct; its smallest reading of ``density_rel_gap`` is the
upper reading that the cell's limit is set below (PERF.md).  A mine
cell's control needs no window and runs on the host alone.  It is not
part of a benchmark run.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import ml_dtypes  # noqa: E402

from benchlib import compare, harness, reference, tables  # noqa: E402


def control_numbers(cfg: dict, seed: int) -> dict:
    _, tuples, values = tables.make_table(cfg["table"], seed)
    want = reference.mine_config(cfg["mine"], tuples, values)
    got = reference.mine_config(cfg["mine"], tuples, values,
                                density_dtype=ml_dtypes.bfloat16)
    numbers = compare.mine_numbers(got, want)
    # the control answers alike every time it is asked
    numbers["repeat_mismatch"] = 0
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = {w["name"]: w for w in harness.load_benchmark()["workloads"]}
    cfg = harness.load_json("configs", spec[args.workload]["config"])
    limits = harness.load_json("cells", args.workload)["limits"]
    for seed in args.seeds:
        numbers = control_numbers(cfg, seed)
        ok, table = compare.judge(numbers, limits)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": ok, "checks": table}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
