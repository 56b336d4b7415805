"""Device milliseconds per mine in the all-gathers and all-reduces:
Stage 3's gather of every shard's signatures and the range
partitioner's histogram and link checks, the mean over the cell's chips
(``benchlib/collectives.py``)."""
from benchlib.collectives import GATHER_REDUCE, ms_per_mine


def read(trace, facts, peaks):
    return ms_per_mine(trace, facts, GATHER_REDUCE)
