"""Device milliseconds per mine spent in Stage 2's component operators
(``prime_components`` / ``delta_components``, the δ-window searches
included): the summed device time of the operations whose HLO
``op_name`` path holds the ``stage2_components`` named scope of
``core/pipeline.py``, divided by the mines of the traced window. Needs a
summary that carries ``scopes`` (``benchlib/scopes.py``); None without."""
from benchlib.scopes import scope_ms_per_mine


def read(trace, facts, peaks):
    return scope_ms_per_mine(trace, facts, "stage2_components")
