"""Engine registry: one front-end for every (backend, variant) pair.

``repro.core.mine(ctx, backend=..., variant=...)`` is the single entry
point the launchers, serving surface and benchmarks use instead of
importing backends directly.  Engines register themselves under a
``(backend, variant)`` key; unknown combinations fail with an error that
lists every valid choice.

Backends: ``batch`` (single shard), ``distributed`` (shard_map mesh,
'replicate' or 'shuffle' merge), ``streaming`` (incremental sorted-run
ingestion), ``reference`` (pure-python oracle).
Variants: ``prime`` (OAC/multimodal) and ``noac`` (many-valued δ).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import numpy as np

from ..obs.phases import phase
from .batch import BatchMiner
from .context import PolyadicContext
from .distributed import DistributedMiner, pad_tuples, pad_values
from .manyvalued import NOACMiner
from .streaming import StreamingMiner

BACKENDS = ("batch", "distributed", "streaming", "reference")
VARIANTS = ("prime", "noac")

_REGISTRY: dict[tuple[str, str], Callable] = {}


def register_engine(backend: str, variant: str):
    """Class decorator-style registration of an engine runner."""
    def deco(fn):
        _REGISTRY[(backend, variant)] = fn
        return fn
    return deco


def available_engines() -> list[tuple[str, str]]:
    """Sorted (backend, variant) pairs with a registered engine."""
    return sorted(_REGISTRY)


def resolve_engine(backend: str, variant: str) -> Callable:
    try:
        return _REGISTRY[(backend, variant)]
    except KeyError:
        valid = ", ".join(f"{b}/{v}" for b, v in available_engines())
        raise ValueError(
            f"no engine for backend={backend!r} variant={variant!r}; "
            f"valid combinations: {valid}") from None


@dataclasses.dataclass
class MineRun:
    """Outcome of one ``mine()`` call."""
    backend: str
    variant: str
    n_clusters: int              # kept clusters
    elapsed_s: float             # wall time of the first mining execution
                                 # (includes jit compile; excludes miner
                                 # construction and materialisation)
    result: Any                  # backend-native result object (or None)
    miner: Any                   # the engine instance (None for reference)
    rerun: Any = None            # zero-arg warm re-execution of the mining
                                 # step (no re-compile); returns the result
                                 # and records its time in ``rerun.last_s``
    _clusters: Any = None        # list, or a zero-arg callable making it

    @property
    def clusters(self) -> Optional[list]:
        """[(components, density), ...] of the kept clusters, or None
        (distributed).  Materialised on first access: at published
        scale every cluster's component sets take tens of GB of host
        memory, far more than the mine itself."""
        if callable(self._clusters):
            self._clusters = self._clusters()
        return self._clusters


def mine(ctx: PolyadicContext, backend: str = "batch",
         variant: str = "prime", **params) -> MineRun:
    """Mine ``ctx`` with the selected backend/variant.

    Common params: ``theta`` (prime min density), ``delta``/``rho_min``/
    ``minsup`` (noac), ``seed``, ``packed`` (packed-key sort path; None =
    auto, False = lexsort baseline), ``sort_backend`` ('radix' — the
    bit-plan-pruned LSD default — | 'lax' | 'lexsort'), ``use_pallas``
    (fused Pallas kernels; None = on TPU only).  Backend-specific:
    ``mesh``/``axes``/``strategy``/``capacity_factor``/``obs``
    (distributed; ``obs`` an enabled ``repro.obs.Obs`` hub that times
    and counts the mine, DESIGN.md §11),
    ``chunks``/``incremental`` (streaming; ``incremental=True`` on the
    distributed backend switches it to chunked ingestion + merged
    per-shard-run snapshots), ``chunk_budget`` (batch: out-of-core
    chunked Stage 1 via ``mine_chunked`` — host-sorted runs, the device
    never sorts), ``window_budget`` (the fully windowed device
    pipeline, DESIGN.md §3c: Stage 1–3 stream through bounded device
    windows; on the batch backend via ``mine_windowed``, on streaming/
    distributed it windows the incremental snapshot remine and sizes
    the shuffle's per-link dispatch batches).  All incremental/chunked
    paths run on the shared ``core.runs`` storage layer (DESIGN.md §4).
    ``variant='noac'`` requires ``delta``.
    """
    if variant == "noac" and params.get("delta") is None:
        raise ValueError("variant='noac' requires delta=<float>")
    engine = resolve_engine(backend, variant)
    t0 = time.perf_counter()
    n_clusters, clusters, result, miner, rerun = engine(ctx, params)
    total = time.perf_counter() - t0
    elapsed = getattr(rerun, "last_s", None) or total
    return MineRun(backend=backend, variant=variant, n_clusters=n_clusters,
                   elapsed_s=elapsed, result=result, miner=miner,
                   rerun=rerun, _clusters=clusters)


def _noac_ctx(ctx: PolyadicContext) -> PolyadicContext:
    """NOAC precondition: deduplicated, with a value column (§3.2: W={0,1},
    δ=0 degenerates to prime operators when values are absent)."""
    if ctx.values is None:
        ctx = PolyadicContext(ctx.sizes, ctx.tuples,
                              np.zeros(ctx.num_tuples, np.float32), ctx.names)
    return ctx.deduplicated()


# ---------------------------------------------------------------------------
# Engine runners.  Each returns (n_clusters, clusters, result, miner, rerun)
# where ``clusters`` is the materialised list or a zero-arg callable that
# makes it, and ``rerun`` re-executes the mining step warm (no re-compile).
# ---------------------------------------------------------------------------

def _pipe_kw(p):
    """Pipeline-core params shared by every jax backend."""
    return {"packed": p.get("packed"),
            "sort_backend": p.get("sort_backend"),
            "use_pallas": p.get("use_pallas"),
            "prune_values": p.get("prune_values", True),
            "window_budget": p.get("window_budget")}


def _timed(step, block=True):
    """Wrap a mining step: each call blocks on the device result (when it
    has one, in a ``mine.wait`` phase) and records its wall time in
    ``go.last_s``."""
    def go():
        t0 = time.perf_counter()
        out = step()
        if block:
            with phase("mine.wait"):
                np.asarray(out.keep)
        go.last_s = time.perf_counter() - t0
        return out
    go.last_s = None
    return go


def _batch_step(miner, p, tuples, values=None):
    """One-shot in-core mining; out-of-core chunked Stage 1 when
    ``chunk_budget`` is set (``PipelineMiner.mine_chunked``); the fully
    windowed device pipeline when ``window_budget`` is set
    (``PipelineMiner.mine_windowed`` — host run sort *and* bounded
    device windows sharing the one budget)."""
    wb = p.get("window_budget")
    if wb:
        return lambda: miner.mine_windowed(tuples, values=values,
                                           window_budget=int(wb))
    budget = p.get("chunk_budget")
    if budget:
        return lambda: miner.mine_chunked(tuples, values=values,
                                          chunk_budget=int(budget))
    if values is not None:
        return lambda: miner(tuples, values)
    return lambda: miner(tuples)


def _lazy_clusters(miner, rerun):
    """Run the mining step once; the engine-runner tuple of its result:
    the kept count now, the component sets when first asked for."""
    res = rerun()
    return (int(np.asarray(res.keep).sum()),
            lambda: miner.materialise(res), res, miner, rerun)


@register_engine("batch", "prime")
def _batch_prime(ctx, p):
    miner = BatchMiner(ctx.sizes, theta=p.get("theta", 0.0),
                       seed=p.get("seed", 0x5EED), **_pipe_kw(p))
    return _lazy_clusters(miner, _timed(_batch_step(miner, p, ctx.tuples)))


@register_engine("batch", "noac")
def _batch_noac(ctx, p):
    ctx = _noac_ctx(ctx)
    miner = NOACMiner(ctx.sizes, delta=p["delta"],
                      rho_min=p.get("rho_min", 0.0),
                      minsup=p.get("minsup", 0), seed=p.get("seed", 0x5EED),
                      **_pipe_kw(p))
    return _lazy_clusters(
        miner, _timed(_batch_step(miner, p, ctx.tuples, ctx.values)))


def _local_mesh():
    from ..launch.mesh import make_local_mesh
    return make_local_mesh()


def _run_distributed(ctx, p, values, **variant_kw):
    mesh = p.get("mesh") or _local_mesh()
    miner = DistributedMiner(
        ctx.sizes, mesh, axes=p.get("axes", "data"),
        strategy=p.get("strategy", "replicate"),
        capacity_factor=p.get("capacity_factor", 2.0),
        seed=p.get("seed", 0x5EED), obs=p.get("obs"), **_pipe_kw(p),
        **variant_kw)
    if p.get("incremental"):
        # chunked ingestion + merged per-shard-run snapshot (core.runs)
        step = -(-ctx.num_tuples // max(1, int(p.get("chunks", 8))))

        def ingest_and_snapshot():
            miner.reset_stream()
            for lo in range(0, ctx.num_tuples, step):
                hi = lo + step
                miner.ingest(ctx.tuples[lo:hi],
                             values[lo:hi] if values is not None else None)
            return miner.snapshot()

        rerun = _timed(ingest_and_snapshot)
    else:
        tuples = pad_tuples(ctx.tuples, miner.n_shards)
        values = (pad_values(values, miner.n_shards)
                  if values is not None else None)
        rerun = _timed(lambda: miner(tuples, values))
    res = rerun()
    return int(np.asarray(res.keep).sum()), None, res, miner, rerun


@register_engine("distributed", "prime")
def _distributed_prime(ctx, p):
    return _run_distributed(ctx, p, None, theta=p.get("theta", 0.0))


@register_engine("distributed", "noac")
def _distributed_noac(ctx, p):
    ctx = _noac_ctx(ctx)
    return _run_distributed(ctx, p, ctx.values, delta=p["delta"],
                            rho_min=p.get("rho_min", 0.0),
                            minsup=p.get("minsup", 0))


def _run_streaming(ctx, p, values, **variant_kw):
    miner = StreamingMiner(ctx.sizes, seed=p.get("seed", 0x5EED),
                           incremental=p.get("incremental", True),
                           **_pipe_kw(p), **variant_kw)
    chunks = max(1, int(p.get("chunks", 8)))
    step = -(-ctx.num_tuples // chunks)

    def ingest_and_snapshot():
        miner.state = None
        for lo in range(0, ctx.num_tuples, step):
            hi = lo + step
            miner.add(ctx.tuples[lo:hi],
                      values[lo:hi] if values is not None else None)
        return miner.snapshot()

    return _lazy_clusters(miner, _timed(ingest_and_snapshot))


@register_engine("streaming", "prime")
def _streaming_prime(ctx, p):
    return _run_streaming(ctx, p, None, theta=p.get("theta", 0.0))


@register_engine("streaming", "noac")
def _streaming_noac(ctx, p):
    ctx = _noac_ctx(ctx)
    return _run_streaming(ctx, p, ctx.values, delta=p["delta"],
                          rho_min=p.get("rho_min", 0.0),
                          minsup=p.get("minsup", 0))


@register_engine("reference", "prime")
def _reference_prime(ctx, p):
    from . import reference as R
    rerun = _timed(lambda: R.multimodal_clusters(ctx,
                                                 theta=p.get("theta", 0.0)),
                   block=False)
    _, _, density, kept = rerun()
    clusters = [(cl, density[tuple(tuple(sorted(c)) for c in cl)])
                for cl in kept]
    return len(clusters), clusters, None, None, rerun


@register_engine("reference", "noac")
def _reference_noac(ctx, p):
    from . import reference as R
    ctx = _noac_ctx(ctx)
    rerun = _timed(lambda: R.noac(ctx, p["delta"],
                                  rho_min=p.get("rho_min", 0.0),
                                  minsup=p.get("minsup", 0)), block=False)
    kept = rerun()
    clusters = [(cl, float("nan")) for cl in kept]
    return len(clusters), clusters, None, None, rerun
