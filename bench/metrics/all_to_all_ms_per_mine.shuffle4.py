"""Device milliseconds per mine in the shuffle's ``all_to_all``
exchanges (records out to their owners, answers back, per mode), the
mean over the cell's chips (``benchlib/collectives.py``)."""
from benchlib.collectives import ALL_TO_ALL, ms_per_mine


def read(trace, facts, peaks):
    return ms_per_mine(trace, facts, ALL_TO_ALL)
