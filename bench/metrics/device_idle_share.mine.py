"""Share of the traced window in which no operation ran on the device
(mean over the chips used), in percent: 1 - busy / window, busy being
the union of the device-operation intervals of the profiler trace."""
from benchlib.trace import idle_share_percent


def read(trace, facts, peaks):
    return idle_share_percent(trace)
