"""Peak device-allocation probe for the windowed pipeline benchmarks.

Two measurement sources, chosen by what the backend keeps:

* ``"allocator"`` — ``device.memory_stats()["bytes_in_use"]``, real
  allocator telemetry.  Accelerator backends (TPU/GPU) expose it, and on
  them it is the only source: a device without it is an error, never a
  silent switch to a host-side estimate.
* ``"live_arrays"`` — XLA-CPU keeps no allocator statistics, so there
  the summed ``nbytes`` of ``jax.live_arrays()`` is an exact census of
  *materialised* arrays.  Sampled at stage boundaries it misses
  transient compiler scratch, but that scratch is itself sized by the
  operand shapes being compared, so the O(window)-vs-O(T) contrast the
  benchmark gates on survives the approximation.

``MemProbe`` is the ``probe`` callback of ``core.windowed``: call it
with a stage name at each sampling point; ``peak_bytes`` / ``stages``
report high-water deltas from the construction-time baseline, and
``source`` names where the bytes came from.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax


def device_bytes() -> Tuple[int, str]:
    """Current device allocation in bytes, and the source it was read
    from (``"allocator"`` or ``"live_arrays"``, see module doc)."""
    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if stats and "bytes_in_use" in stats:
        return int(stats["bytes_in_use"]), "allocator"
    if dev.platform != "cpu":
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            "allocator bytes_in_use")
    return int(sum(a.nbytes for a in jax.live_arrays())), "live_arrays"


class MemProbe:
    """High-water allocation tracker relative to a baseline sample."""

    def __init__(self):
        self.baseline, self.source = device_bytes()
        self.stages: Dict[str, int] = {}
        self.peak_bytes = 0

    def __call__(self, stage: str = "total") -> int:
        delta = max(0, device_bytes()[0] - self.baseline)
        self.stages[stage] = max(self.stages.get(stage, 0), delta)
        self.peak_bytes = max(self.peak_bytes, delta)
        return delta

    def report(self) -> Dict[str, object]:
        return {"source": self.source, "peak_bytes": int(self.peak_bytes),
                "stages": {k: int(v) for k, v in sorted(self.stages.items())}}


def measure_result_bytes(result) -> int:
    """Device bytes held live by a result pytree (0 for host/numpy
    leaves) — what a monolithic run keeps resident after it returns."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(result):
        if isinstance(leaf, jax.Array):
            total += int(leaf.nbytes)
    return total
