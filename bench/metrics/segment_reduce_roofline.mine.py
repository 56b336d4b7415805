"""Share of the HBM roofline reached by the Stage 2 segment-reduce
kernel (``kernels/segment_reduce.py``), in percent: the least time its
bytes take at the chip's HBM bandwidth (``benchlib/work.py``), over
the summed device time of its events in the trace.  The kernel is
memory-bound, so HBM bandwidth is the bound."""
from benchlib.trace import op_seconds

#: The trace names a Pallas kernel by its HLO text only; this one is
#: told by its signature: three lane-dense (rows, 128) int32 prefix sums
#: written from three such streams.
KERNELS = (
    r"= \(s32\[\d+,128\]\{[^}]*\}, s32\[\d+,128\]\{[^}]*\}, "
    r"s32\[\d+,128\]\{[^}]*\}\) custom-call\(.*tpu_custom_call",
)


def read(trace, facts, peaks):
    dev = trace["devices"][sorted(trace["devices"])[0]]
    seconds = op_seconds(dev, KERNELS)
    if seconds <= 0 or not facts.get("mines"):
        return None
    least = facts["segment_reduce_bytes_per_mine"] * facts["mines"] \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
