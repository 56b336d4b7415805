"""Device time of the collective operations in a profiler trace.

The trace names each operation by its HLO instruction, e.g.
``%all_to_all.61 = u32[4,3125012,2]{...} all-to-all(...)``, or by the
instruction's name alone.  A collective is told by its instruction's
name or opcode, synchronous or as the ``-start``/``-done`` pair of an
asynchronous one.  XLA may build an all-gather as an all-reduce, so the
two are read together.  The readers of the shuffle cells take the mean
over the chips of the summed durations, per mine.
"""
from __future__ import annotations

from .trace import op_seconds


def patterns(*opcodes: str) -> tuple:
    """Regular expressions of the instructions of ``opcodes``."""
    out = []
    for op in opcodes:
        name = op.replace("-", "[-_]")
        out.append(rf"^%?{name}([-_]start|[-_]done)?[.\d]*( = |$)")
        out.append(rf" {op}(-start|-done)?\(")
    return tuple(out)


ALL_TO_ALL = patterns("all-to-all")
GATHER_REDUCE = patterns("all-gather", "all-reduce")


def ms_per_mine(summary: dict, facts: dict, pats) -> float | None:
    """Milliseconds a mine of the operations matching ``pats``, the
    mean over the traced devices; None where there are none."""
    devices = summary["devices"]
    if not devices or not facts.get("mines"):
        return None
    seconds = sum(op_seconds(ev, pats) for ev in devices.values())
    if seconds <= 0:
        return None
    return 1e3 * seconds / len(devices) / facts["mines"]
