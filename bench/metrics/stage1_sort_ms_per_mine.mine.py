"""Device milliseconds per mine spent in Stage 1's per-mode sorts
(``sort_mode``: key packing, the radix passes and their kernels,
segmentation): the summed device time of the operations whose HLO
``op_name`` path holds the ``stage1_sort`` named scope of
``core/pipeline.py``, divided by the mines of the traced window. Needs a
summary that carries ``scopes`` (``benchlib/scopes.py``); None without."""
from benchlib.scopes import scope_ms_per_mine


def read(trace, facts, peaks):
    return scope_ms_per_mine(trace, facts, "stage1_sort")
