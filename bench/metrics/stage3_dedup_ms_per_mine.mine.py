"""Device milliseconds per mine spent in Stage 3 (`stage3_dedup`: the
signature sort, generating-tuple counts, density and keep): the summed
device time of the operations whose HLO ``op_name`` path holds the
``stage3_dedup`` named scope of ``core/pipeline.py``, divided by the
mines of the traced window. Needs a summary that carries ``scopes``
(``benchlib/scopes.py``); None without."""
from benchlib.scopes import scope_ms_per_mine


def read(trace, facts, peaks):
    return scope_ms_per_mine(trace, facts, "stage3_dedup")
