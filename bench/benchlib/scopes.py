"""Reduction of a profiler trace by the program's own names.

``trace.load_xplane`` keeps each device operation as ``[name, start_ns,
dur_ns]`` and the harness's ``bench.*`` host spans.  ``load`` here keeps
the same triples and adds what the program names itself:

* ``scopes``: per device, the ``op_name`` of each operation's HLO
  instruction, in event order — the ``jax.named_scope`` path, e.g.
  ``jit(mine_tuples)/stage2_components/delta_search/gather``.  The
  compiled modules travel in the trace's ``/host:metadata`` plane as
  serialized ``HloProto``s; an event is joined to its instruction by
  (module, instruction name), never by matching the HLO text.
* ``host``: the ``bench.*`` spans and the program's ``repro.*`` spans
  (``repro.obs.phase``), so an idle gap is labelled by the innermost
  phase running on the host.

``gc_spans`` marks the interpreter's garbage collections as ``bench.gc``
host spans, so a stall can name itself.  The metric readers
``metrics/<stage>_ms_per_mine.mine.py`` and
``metrics/host_prep_ms_per_mine.mine.py`` read a summary that carries
these ``scopes`` and ``host`` keys, and return None on one without.
"""
from __future__ import annotations

import bisect
import contextlib
import gc
import re

from . import trace as TR

#: the top-level named scopes of ``repro.core.pipeline.mine_tuples``
STAGES = ("stage1_sort", "stage2_components", "stage2_mix", "stage3_dedup")
#: host spans kept: the harness's and the program's
HOST_PREFIXES = ("bench.", "repro.")
#: the program's host phases before a mine's device work can start
PREP_SPANS = ("repro.mine.copy_in", "repro.mine.value_domain",
              "repro.mine.dispatch")

_MODULES_LINE = "XLA Modules"
_PROGRAM = re.compile(r"^(.*?)\((\d+)\)$")


# -- a minimal protobuf reader (XSpace, HloProto) ---------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one serialized message: an int for
    varints, a memoryview for every other wire type."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _op_names(hlo_proto) -> dict:
    """{instruction: op_name} of one ``HloProto`` (module 1; its
    computations 3, their instructions 2, each with name 1 and metadata
    7, whose op_name is 2)."""
    ops = {}
    for num, module in _fields(hlo_proto):
        if num != 1:
            continue
        for f, v in _fields(module):
            if f == 3:
                for g, ins in _fields(v):
                    if g != 2:
                        continue
                    iname, op = None, ""
                    for h, w in _fields(ins):
                        if h == 1:
                            iname = _text(w)
                        elif h == 7:
                            op = next((_text(x) for k, x in _fields(w)
                                       if k == 2), "")
                    if iname is not None:
                        ops[iname] = op
    return ops


def hlo_op_names(path: str) -> dict:
    """{program id or module name: {instruction name: op_name}} of every
    HLO module the trace file's ``/host:metadata`` plane carries (an
    event metadata named ``<module>(<program id>)`` with an "Hlo Proto"
    stat)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    modules = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for n, v in fields if n == 2), "") \
                != "/host:metadata":
            continue
        stat_names = {}
        for n, entry in fields:
            if n == 5:       # stat_metadata: key 1, XStatMetadata 2
                for k, meta in _fields(entry):
                    if k == 2:
                        d = dict(_fields(meta))
                        stat_names[d.get(1)] = _text(d.get(2, b""))
        for n, entry in fields:
            if n != 4:       # event_metadata: key 1, XEventMetadata 2
                continue
            for k, meta in _fields(entry):
                if k != 2:
                    continue
                name = ""
                for m, v in _fields(meta):
                    if m == 2:
                        name = _text(v)
                    elif m == 5:
                        d = dict(_fields(v))
                        if _is_hlo_proto(stat_names.get(d.get(1))) \
                                and 6 in d:
                            ops = _op_names(d[6])
                            for key in _program_keys(name):
                                modules.setdefault(key, {}).update(ops)
    return modules


def _is_hlo_proto(stat_name) -> bool:
    return (stat_name or "").lower().replace(" ", "_") == "hlo_proto"


def _program_keys(name: str) -> list:
    """[program id, module name] of ``<module>(<id>)``, else [name]."""
    m = _PROGRAM.match(name)
    return [int(m.group(2)), m.group(1)] if m else [name]


# -- loading -----------------------------------------------------------------

_INSTR = re.compile(r"^%?([^\s=]+) = ")


def _module_keys(stats: dict, enclosing: str) -> list:
    keys = []
    if "program_id" in stats:
        keys.append(int(stats["program_id"]))
    if "hlo_module" in stats:
        keys.append(str(stats["hlo_module"]))
    if enclosing:
        keys += _program_keys(enclosing)
    return keys


def _op_name(modules: dict, keys: list, instr: str) -> str:
    for k in keys:
        ops = modules.get(k)
        if ops is not None and instr in ops:
            return ops[instr]
    hits = {ops[instr] for ops in modules.values() if instr in ops}
    return hits.pop() if len(hits) == 1 else ""


def load(path: str, device_ids=None, device_plane: str = "/device:TPU:",
         ops_line: str = TR.OPS_LINE) -> dict:
    """``trace.load_xplane``'s ``devices`` and ``host`` (with ``repro.*``
    spans kept too) plus ``scopes``: per device, the ``op_name`` of each
    event's instruction ("" where the trace's modules do not name it).
    ``device_plane`` and ``ops_line`` are prefixes of the plane and line
    names that hold the device's operations."""
    from jax.profiler import ProfileData
    modules = hlo_op_names(path)
    devices, scopes, host = {}, {}, []
    for plane in ProfileData.from_file(path).planes:
        name = plane.name
        if name.startswith(device_plane):
            dev = name[len(device_plane):].split(":")[-1] or "0"
            if device_ids is not None and dev not in device_ids:
                continue
            spans = []
            for line in plane.lines:
                if line.name == _MODULES_LINE:
                    spans = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events)
            starts = [s[0] for s in spans]
            for line in plane.lines:
                if not line.name.startswith(ops_line):
                    continue
                ev, sc = devices.setdefault(dev, []), scopes.setdefault(dev, [])
                for e in line.events:
                    if e.name.startswith("end: "):
                        continue
                    stats = dict(e.stats)
                    j = bisect.bisect_right(starts, e.start_ns) - 1
                    enclosing = (spans[j][2] if j >= 0
                                 and e.start_ns < spans[j][1] else "")
                    m = _INSTR.match(e.name)
                    instr = str(stats.get("hlo_op", m.group(1) if m
                                          else e.name))
                    ev.append([e.name, e.start_ns, e.duration_ns])
                    sc.append(_op_name(modules,
                                       _module_keys(stats, enclosing),
                                       instr))
        if name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return {"devices": devices, "scopes": scopes, "host": host}


def summarize(trace: dict, window_s: float, top: int = 10) -> dict:
    """``trace.summarize`` of a loaded trace, with its ``scopes`` and
    ``host`` carried along for the readers."""
    out = TR.summarize(trace, window_s, top)
    out["scopes"] = trace["scopes"]
    out["host"] = trace["host"]
    return out


# -- reductions --------------------------------------------------------------

def stage_of(op_name: str) -> str:
    """The top-level stage scope of an ``op_name`` path, or "" (the
    first, where XLA merged the same work of two stages)."""
    return next((p for p in op_name.split("/") if p in STAGES), "")


def scope_seconds(summary: dict, scope: str):
    """Device seconds (first device) of the operations under ``scope``
    anywhere in their path; None where the summary has no scopes."""
    scopes = summary.get("scopes")
    if not scopes:
        return None
    dev = sorted(summary["devices"])[0]
    return sum(float(d) for (_, _, d), op in zip(summary["devices"][dev],
                                                 scopes.get(dev, []))
               if scope in op.split("/")) / 1e9


def stage_seconds(summary: dict) -> dict:
    """{stage: device seconds} (first device), "" for operations under
    no stage scope."""
    dev = sorted(summary["devices"])[0]
    out = {}
    for (_, _, d), op in zip(summary["devices"][dev],
                             summary["scopes"].get(dev, [])):
        k = stage_of(op)
        out[k] = out.get(k, 0.0) + float(d) / 1e9
    return out


def scope_ms_per_mine(summary: dict, facts: dict, scope: str):
    """Device milliseconds per mine under ``scope``, or None."""
    seconds = scope_seconds(summary, scope)
    if not seconds or not facts.get("mines"):
        return None
    return 1e3 * seconds / facts["mines"]


def span_seconds(summary: dict, names) -> dict:
    """{span name: summed seconds} of the host spans named ``names``."""
    out = {}
    for name, _, d in summary.get("host") or ():
        if name in names:
            out[name] = out.get(name, 0.0) + float(d) / 1e9
    return out


def host_prep_ms_per_mine(summary: dict, facts: dict):
    """Host milliseconds per mine in the program's phases before its
    device work (``PREP_SPANS``), or None where there are none."""
    seconds = sum(span_seconds(summary, PREP_SPANS).values())
    if not seconds or not facts.get("mines"):
        return None
    return 1e3 * seconds / facts["mines"]


@contextlib.contextmanager
def gc_spans():
    """While open, each garbage collection is a ``bench.gc`` span in the
    profiler's host plane (a no-op TraceMe when no profiler runs)."""
    from jax.profiler import TraceAnnotation
    open_spans = []

    def hook(phase, info):
        if phase == "start":
            span = TraceAnnotation("bench.gc", generation=info["generation"])
            span.__enter__()
            open_spans.append(span)
        elif open_spans:
            open_spans.pop().__exit__(None, None, None)

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)
