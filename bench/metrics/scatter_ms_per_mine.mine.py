"""Device milliseconds per mine spent in XLA's scatter fusions: the
rank scatters of the radix sort's passes (``core/radix.py``) and of
Stages 1 and 3 (``core/pipeline.py``), summed from the profiler trace
and divided by the mines of the traced window."""
from benchlib.trace import op_seconds


def pattern(rows: int) -> str:
    """How the trace names one scatter into a table column: a fusion of
    kind kCustom that reads two int32 columns of ``rows`` elements and
    one int32 scalar, and writes ``rows`` elements."""
    col = rf"s32\[{rows}\]\{{[^}}]*\}} %\S+"
    return (rf"^%\S+ = s32\[{rows}\]\{{[^}}]*\}} fusion\({col}, {col}, "
            rf"s32\[\]\{{[^}}]*\}} %\S+\), kind=kCustom,")


def read(trace, facts, peaks):
    dev = trace["devices"][sorted(trace["devices"])[0]]
    seconds = op_seconds(dev, [pattern(facts["rows"])])
    if seconds <= 0 or not facts.get("mines"):
        return None
    return 1e3 * seconds / facts["mines"]
