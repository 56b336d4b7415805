"""``scoped.py`` for the shuffle cells: the same traced run and
reduction, with the sharded program's device stages among the stage
scopes the split attributes device time by.

    python bench/scoped_shuffle.py --workload <cell> --seed <n> \\
        --seconds <s> [--excerpt <path>] [--keep-trace <path>]
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import scoped  # noqa: E402
from benchlib import scopes  # noqa: E402

#: the top-level named scopes of ``DistributedMiner._body_shuffle``
SHUFFLE_STAGES = ("shuffle_route", "shuffle_exchange", "shuffle_owner",
                  "stage2_mix", "stage3_gather", "stage3_dedup")


def main(argv=None) -> int:
    scoped.T_START = T_START
    scopes.STAGES = tuple(dict.fromkeys(scopes.STAGES + SHUFFLE_STAGES))
    return scoped.main(argv)


if __name__ == "__main__":
    sys.exit(main())
