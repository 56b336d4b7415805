"""Device milliseconds per mine spent in XLA's gather fusions: the
permutation gathers of the radix sort's passes and payloads
(``core/radix.py``) and the component-window gathers of Stage 2
(``core/pipeline.py``), summed from the profiler trace and divided by
the mines of the traced window."""
import re

from benchlib.trace import op_seconds


def pattern(rows: int) -> str:
    """How the trace names one gather of a table column: a fusion of
    kind kCustom that reads one 1-D column and one int32 index vector
    and writes ``rows`` elements."""
    arr = r"\w+\[\d+\]\{[^}]*\}"
    return (rf"^%\S+ = \w+\[{rows}\]\{{[^}}]*\}} fusion\({arr} %\S+, "
            rf"s32\[\d+\]\{{[^}}]*\}} %\S+\), kind=kCustom,")


def read(trace, facts, peaks):
    dev = trace["devices"][sorted(trace["devices"])[0]]
    seconds = op_seconds(dev, [pattern(facts["rows"])])
    if seconds <= 0 or not facts.get("mines"):
        return None
    return 1e3 * seconds / facts["mines"]
