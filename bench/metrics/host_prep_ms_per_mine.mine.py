"""Host milliseconds per mine in the program's phases before a mine's
device work can start: the summed durations of the ``repro.mine.copy_in``
(table to the device), ``repro.mine.value_domain`` (NOAC's distinct
values) and ``repro.mine.dispatch`` (the jitted pipeline's launch)
spans, divided by the mines of the traced window.  Needs a summary that
carries the program's ``host`` spans (``benchlib/scopes.py``); None
without."""
from benchlib.scopes import host_prep_ms_per_mine


def read(trace, facts, peaks):
    return host_prep_ms_per_mine(trace, facts)
