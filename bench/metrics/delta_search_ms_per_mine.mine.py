"""Device milliseconds per mine spent in the δ-window bound searches of
NOAC's component operator (``delta_search``, nested in
`stage2_components`: value-domain ``searchsorted``, ``keys.search_words``
and their gathers): the summed device time of the operations whose HLO
``op_name`` path holds the ``delta_search`` named scope of
``core/pipeline.py``, divided by the mines of the traced window. Needs a
summary that carries ``scopes`` (``benchlib/scopes.py``); None without."""
from benchlib.scopes import scope_ms_per_mine


def read(trace, facts, peaks):
    return scope_ms_per_mine(trace, facts, "delta_search")
