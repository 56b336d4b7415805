"""Reduction of a profiler trace to device busy time, per-operation
time and idle gaps.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into plain lists: per device, the ``(name, start_ns, dur_ns)`` of every
operation on its "XLA Ops" line, and the host spans whose names start
with ``bench.`` (the harness's own annotations of host phases).
``summarize`` works on those lists alone, so a recorded excerpt
(``bench/data/trace_excerpt.json``) exercises it without a chip.
"""
from __future__ import annotations

import re

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


def load_xplane(path: str, device_ids=None) -> dict:
    """{"devices": {id: [[name, start_ns, dur_ns], ...]}, "host":
    [[name, start_ns, dur_ns], ...]} of one trace file.  ``device_ids``
    keeps only the TPU devices with those ordinals."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            dev = name.split(":")[-1]
            if not dev.isdigit() or (device_ids is not None
                                     and dev not in device_ids):
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(dev, []).extend(
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events)
        elif name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def intervals_union(events) -> list:
    """Sorted disjoint [start, end) intervals covered by the events."""
    spans = sorted((float(s), float(s) + float(d)) for _, s, d in events)
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events) -> float:
    """Nanoseconds in which at least one operation ran."""
    return sum(e - s for s, e in intervals_union(events))


def op_seconds(events, patterns) -> float:
    """Summed device time of the operations whose name matches one of
    the regular expressions ``patterns`` (kernel time: the events of one
    kernel do not overlap)."""
    regs = [re.compile(p) for p in patterns]
    return sum(float(d) for name, _, d in events
               if any(r.search(name) for r in regs)) / 1e9


def kernel_totals(trace: dict) -> dict:
    """Summed seconds per operation name that looks like a custom kernel
    (logged by traced runs, so a renamed kernel shows)."""
    out = {}
    for dev, ev in trace["devices"].items():
        for name, _, d in ev:
            if any(w in name.lower() for w in ("kernel", "custom",
                                                "pallas", "mosaic")):
                out[name] = out.get(name, 0.0) + float(d) / 1e9
    return out


def _host_label(host, t: float) -> str:
    """The innermost harness span running on the host at time ``t``."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside bench spans"


def summarize(trace: dict, window_s: float, top: int = 10) -> dict:
    """busy_s (mean over the devices), window_s, the longest device
    operations and the longest idle gaps of the first device, each gap
    labelled by what the host was doing in its middle."""
    devices = trace["devices"]
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    busy = [busy_ns(ev) for ev in devices.values()]
    totals = {}
    for ev in devices.values():
        for name, _, d in ev:
            totals[name] = totals.get(name, 0.0) + float(d)
    n = len(devices)
    ops = sorted(((k, v / n / 1e9) for k, v in totals.items()),
                 key=lambda kv: -kv[1])[:top]
    first = devices[sorted(devices)[0]]
    spans = intervals_union(first)
    gaps = [(spans[i][1], spans[i + 1][0]) for i in range(len(spans) - 1)
            if spans[i + 1][0] > spans[i][1]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_host_label(trace["host"], (a + b) / 2), (b - a) / 1e9]
            for a, b in gaps[:top]]
    return {"busy_s": sum(busy) / n / 1e9, "window_s": float(window_s),
            "devices": devices, "device_ops": [list(o) for o in ops],
            "idle_gaps": idle}


def idle_share_percent(summary: dict):
    """100 x (1 - busy / window), or None for an empty window."""
    if summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
