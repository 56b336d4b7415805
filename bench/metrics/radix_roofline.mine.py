"""Share of the HBM roofline reached by the radix-sort kernels of
Stages 1 and 3 (``kernels/radix_sort.py``: the histogram sweep and the
per-pass rank kernel), in percent: the least time the bytes the sorts
must move take at the chip's HBM bandwidth (``benchlib/work.py``),
over the summed device time of the kernels' events in the trace.  Both
kernels are memory-bound, so HBM bandwidth is the bound."""
from benchlib.trace import op_seconds

#: The trace names a Pallas kernel by its HLO text only
#: (``%_unknown_.N = ... custom-call(...),
#: custom_call_target="tpu_custom_call"``), so the two kernels are told
#: by their signatures: the histogram sweep writes an (npass, 256) int32
#: table; a rank pass reads a uint32 digit column and 256 bucket starts.
KERNELS = (
    r"= s32\[\d+,256\]\{[^}]*\} custom-call\(.*tpu_custom_call",
    r"= s32\[\d+\]\{[^}]*\} custom-call\(u32\[\d+\]\{[^}]*\} \S+, "
    r"s32\[256\]\{[^}]*\} \S+\), custom_call_target=\"tpu_custom_call\"",
)


def read(trace, facts, peaks):
    dev = trace["devices"][sorted(trace["devices"])[0]]
    seconds = op_seconds(dev, KERNELS)
    if seconds <= 0 or not facts.get("mines"):
        return None
    least = facts["radix_bytes_per_mine"] * facts["mines"] \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
