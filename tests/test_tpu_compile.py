"""The main-path Pallas kernels compile for a TPU v5e chip, under their
own names.

Interpret mode accepts what Mosaic refuses (unaligned lane concatenates,
1-D blocks whose tiling disagrees with XLA's), so every kernel the
engines turn on by themselves on a TPU is compiled here, ahead of time,
for one chip of a described v5e topology — no chip needed.  The
topology is described inside a fixture: only the worker that runs this
file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

#: a full 2^20-row table, one window of BibSonomy's 816,197 rows mined
#: in four windows, and a small table mined whole (both ragged: not a
#: whole number of blocks; the short one is less than one default block)
TABLE_ROWS = (1 << 20, 204_050, 3_001)


def _programs(t):
    u32 = (t,), jnp.uint32
    return {
        "segment_reduce": (
            lambda lo, hi, first: ops.segment_reduce(lo, hi, first,
                                                     interpret=False),
            [u32, u32, ((t,), jnp.bool_)]),
        # a 48-bit two-word key: six 8-bit digit passes
        "radix_histogram": (
            lambda hi, lo: ops.radix_histogram(
                (hi, lo), (0, 8, 16, 24, 32, 40), (8,) * 6,
                interpret=False),
            [u32, u32]),
        "radix_rank": (
            lambda digits, starts: ops.radix_rank(digits, starts,
                                                  interpret=False),
            [u32, ((256,), jnp.int32)]),
    }


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the topology, with the persistent compilation cache
    off: entries compiled for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("t", TABLE_ROWS)
@pytest.mark.parametrize("kernel", ["segment_reduce", "radix_histogram",
                                    "radix_rank"])
def test_kernel_compiles_for_v5e(one_chip, kernel, t):
    fn, shapes = _programs(t)[kernel]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's name= is its instruction's name, which a trace shows
    assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text)


@pytest.mark.parametrize("variant", ["prime", "noac"])
def test_mine_ops_keep_their_stage_scope_on_v5e(one_chip, monkeypatch,
                                                 variant):
    """Compiled for the chip with its Mosaic kernels, every operation of
    a mine that carries the program's ``op_name`` lies under a stage
    scope: the scans' fusions too, which ``lax.cum*`` would leave
    unscoped (XLA's own reduce-window trees and copies carry none).
    The NOAC mine's δ-window bounds take the rank-threshold scans,
    under ``stage2_components/delta_search``."""
    from repro.core import BatchMiner, NOACMiner
    from repro.core.pipeline import STAGE_SCOPES, delta_bounds_path
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    t = 8192

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if variant == "prime":
        m, kw = BatchMiner((2337, 67464, 28920), use_pallas=True), {}
    else:
        m = NOACMiner((6040, 3952, 5), delta=1.0, use_pallas=True)
        assert delta_bounds_path(t, 5) == "runs"
        kw = {"values": sds((t,), jnp.float32),
              "value_domain": sds((5,), jnp.float32)}
    text = m._fn.lower(sds((t, 3), jnp.int32),
                       [sds(h.shape, h.dtype) for h in m._lo],
                       [sds(h.shape, h.dtype) for h in m._hi],
                       **kw).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    named = merged = 0
    delta_ops = set()
    for line in entry.splitlines()[1:]:
        name = re.search(r'op_name="([^"]*)"', line)
        op = re.match(r"\s*(?:ROOT )?%\S+ = .*? ([a-z][\w-]*)\(", line)
        if name is None or op.group(1) in ("parameter", "constant", "tuple",
                                           "get-tuple-element", "copy"):
            continue
        path = name.group(1).split("/")
        stages = sum(s in path for s in STAGE_SCOPES)
        assert stages >= 1, line[:300]
        # XLA's CSE may merge the same work of two stages (the NaN test
        # of the value column inside two ``jnp.searchsorted`` calls); a
        # trace gives it to the first stage of its path
        merged += stages > 1
        named += 1
        if "/stage2_components/delta_search/" in name.group(1):
            delta_ops.add(path[-1])
    assert named > 100 and merged <= 0.01 * named, (merged, named)
    # the threshold scans (the searches run none) and the [sm.inv]
    # gathers; a prime mine has no δ-windows
    want = ({"reduce_window_max", "reduce_window_min", "gather"}
            if variant == "noac" else set())
    assert want <= delta_ops and bool(delta_ops) == bool(want), delta_ops


def _callers(text: str) -> dict:
    """Stack frame id → the Python functions of the frame, innermost
    first, from a compiled module's ``FunctionNames``, ``FileLocations``
    and ``StackFrames`` tables (a frame's ``parent_frame_id`` is the
    parent's id plus one; 0 is none)."""
    def table(title, pattern):
        body = text[text.index(f"\n{title}\n"):].split("\n\n")[0]
        return {int(m.group(1)): m.groups()[1:]
                for m in re.finditer(pattern, body)}
    fns = table("FunctionNames", r'\n(\d+) "([^"]*)"')
    locs = table("FileLocations", r"\n(\d+) \{.*?function_name_id=(\d+)")
    frames = table("StackFrames",
                   r"\n(\d+) \{file_location_id=(\d+) parent_frame_id=(\d+)")
    out = {}
    for f in frames:
        chain, g = [], f
        while g in frames and len(chain) < len(frames):
            loc, parent = frames[g]
            chain.append(fns[int(locs[int(loc)][0])][0])
            g = int(parent) - 1
        out[f] = chain
    return out


def test_shuffle_compiles_for_a_v5e_host(topo, monkeypatch):
    """The NOAC shuffle of a two-word key over D = 10 half stars,
    compiled for the four chips of a described v5e host: the records
    cross the chips by all-to-all (out and back, per mode), every
    operation that carries the program's ``op_name`` lies under one of
    the shuffle's stage scopes, and each owner stage's δ-windows are
    the rank-threshold scans — no search loop under ``delta_search``.
    The dispatch ranks records by per-owner running counts: the one
    loop under ``shuffle_route`` is the value lane's search of the
    D-value domain (``ModeKeyPlan.value_lane``, as on the batch path),
    and nothing there sorts."""
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import DistributedMiner
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        mesh = Mesh(np.asarray(topo.devices), ("data",))
        m = DistributedMiner((162541, 62423, 10), mesh, strategy="shuffle",
                             delta=1.0, use_pallas=True)
        t = 16384

        def sds(shape, dtype, spec):
            return jax.ShapeDtypeStruct(shape, dtype,
                                        sharding=NamedSharding(mesh, spec))
        text = m._build(t).lower(
            sds((t, 3), jnp.int32, P("data", None)),
            sds((t,), jnp.float32, P("data")),
            sds((10,), jnp.float32, P()),
            [sds(h.shape, h.dtype, P()) for h in m._lo],
            [sds(h.shape, h.dtype, P()) for h in m._hi]).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    stages = ("shuffle_route", "shuffle_exchange", "shuffle_owner",
              "stage2_mix", "stage3_gather", "stage3_dedup")
    assert len(re.findall(r" all-to-all\(", text)) == 9
    callers, route_loops = _callers(text), []
    for line in text.splitlines():
        op = re.match(r"\s*(?:ROOT )?%\S+ = .*? (while|sort)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if op and name and "shuffle_route" in name.group(1).split("/"):
            frame = re.search(r"stack_frame_id=(\d+)", line)
            fns = callers.get(int(frame.group(1)), []) if frame else []
            route_loops.append((op.group(1), fns[:1]))
    assert all(loop == ("while", ["ModeKeyPlan.value_lane"])
               for loop in route_loops), route_loops
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    named, delta_ops = 0, set()
    for line in entry.splitlines()[1:]:
        name = re.search(r'op_name="([^"]*)"', line)
        op = re.match(r"\s*(?:ROOT )?%\S+ = .*? ([a-z][\w-]*)\(", line)
        if name is None or op is None or op.group(1) in (
                "parameter", "constant", "tuple", "get-tuple-element",
                "copy"):
            continue
        path = name.group(1).split("/")
        if not any(s in path for s in stages):
            # only XLA's own instructions (reduce-window trees of the
            # scans, their slices) inherit the bare shard_map path
            assert re.search(r"-|\.\d+$", path[-1]), line[:300]
            continue
        named += 1
        if "delta_search" in path:
            delta_ops.add(path[-1])
    assert named > 100
    assert {"reduce_window_max", "reduce_window_min"} <= delta_ops
    assert "while" not in delta_ops, delta_ops
