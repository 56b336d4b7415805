"""The mining path's own names on the profiler's clock (DESIGN.md §11):
``repro.obs.phase`` spans and timings, the ``repro.mine.*`` spans of a
rerun in a CPU profiler trace, and the named stage scopes every
operation of the compiled pipeline carries."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro._compat import cumulative
from repro.core import BatchMiner, NOACMiner, PolyadicContext, mine
from repro.core.pipeline import STAGE_SCOPES
from repro.core.windowed import STAGES as WINDOW_STAGES
from repro.obs import NULL_OBS, Obs, phase

SIZES = (20, 30, 15)


def _table(n=400, seed=0):
    rng = np.random.default_rng(seed)
    t = np.stack([rng.integers(0, s, n) for s in SIZES], 1).astype(np.int32)
    return t, rng.integers(1, 6, n).astype(np.float32)


def _stage_hist(obs, metric="pipeline_stage_ms"):
    """{stage label: observations} of one histogram family of the hub."""
    doc = obs.metrics.to_dict().get(metric, {"series": []})
    return {r["labels"]["stage"]: r["count"] for r in doc["series"]}


# -- the helper ---------------------------------------------------------------

def test_phase_records_nothing_into_a_disabled_hub():
    off = Obs.create()
    off.enabled = False
    for hub in (None, NULL_OBS, off):
        with phase("mine.copy_in", hub, bytes=12) as ph:
            pass
        assert ph.ms is None
    assert off.metrics.sample_count() == 0


def test_phase_times_into_the_hub():
    obs = Obs.create()
    with phase("mine.dispatch", obs) as ph:
        pass
    with phase("window.stage2_mix", obs, metric="pipeline_window_ms",
               stage="stage2_mix"):
        pass
    assert ph.ms is not None and ph.ms >= 0
    assert _stage_hist(obs) == {"mine.dispatch": 1}
    assert _stage_hist(obs, "pipeline_window_ms") == {"stage2_mix": 1}


def test_miner_phases_with_a_hub():
    """With a hub the monolithic call times its phases (the wait
    included); the windowed path keeps its per-window histograms."""
    t, v = _table()
    m = NOACMiner(SIZES, delta=1.0)
    m.obs = Obs.create()
    m(t, v)
    assert _stage_hist(m.obs) == {"mine.value_domain": 1, "mine.copy_in": 1,
                                  "mine.dispatch": 1, "mine.wait": 1}
    w = BatchMiner(SIZES)
    w.obs = Obs.create()
    w.mine_windowed(t, window_budget=128)
    windows = -(-len(t) // 128)
    per_window = _stage_hist(w.obs, "pipeline_window_ms")
    assert per_window == {"stage1_scan": windows * len(SIZES),
                          "stage2_mix": windows, "stage3_sort": windows}
    assert _stage_hist(w.obs) == {"stage1_sort": 1,
                                  **{s: 1 for s in WINDOW_STAGES}}


def test_cumulative_is_the_lax_scan():
    rng = np.random.default_rng(1)
    for dt in (np.int32, np.uint32):
        x = jnp.asarray(rng.integers(0, 2**31 - 1, 3000).astype(dt))
        assert np.array_equal(cumulative(x, jax.lax.add),
                              jnp.cumsum(x, dtype=dt))
        assert np.array_equal(cumulative(x, jax.lax.max), jax.lax.cummax(x))
        assert np.array_equal(cumulative(x, jax.lax.min, reverse=True),
                              jax.lax.cummin(x, reverse=True))
    x2 = jnp.asarray(rng.integers(0, 3, (300, 7)).astype(np.int32))
    assert np.array_equal(cumulative(x2, jax.lax.add, axis=0),
                          jnp.cumsum(x2, axis=0, dtype=jnp.int32))


# -- spans in a profiler trace ------------------------------------------------

def _host_spans(tmp_path, fn) -> list:
    """[(name, start_ns, end_ns, args)] of the ``repro.*``/``test.*``
    host spans of a CPU trace of ``fn()``."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith(("repro.", "test."))]
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("variant", ["prime", "noac"])
def test_rerun_spans_in_the_trace(tmp_path, variant):
    t, v = _table()
    ctx = PolyadicContext(SIZES, t, v if variant == "noac" else None)
    kw = {"delta": 1.0} if variant == "noac" else {}
    run = mine(ctx, backend="batch", variant=variant, **kw)

    def one_mine():
        with jax.profiler.TraceAnnotation("test.mine"):
            run.rerun()
    spans = _host_spans(tmp_path, one_mine)
    (_, m0, m1, _), = [s for s in spans if s[0] == "test.mine"]
    names = [s[0] for s in spans if s[0] != "test.mine"]
    want = (["repro.mine.value_domain"] if variant == "noac" else []) + \
        ["repro.mine.copy_in", "repro.mine.dispatch", "repro.mine.wait"]
    assert names == want
    inner = [s for s in spans if s[0] != "test.mine"]
    for (_, a0, a1, _), (_, b0, _, _) in zip(inner, inner[1:]):
        assert a1 <= b0                     # one after the other
    assert all(m0 <= s[1] and s[2] <= m1 for s in inner)
    copy_in = next(s for s in inner if s[0] == "repro.mine.copy_in")
    rows = ctx.deduplicated().num_tuples if variant == "noac" \
        else ctx.num_tuples
    cols = 3 + (variant == "noac")
    assert int(copy_in[3]["bytes"]) == 4 * rows * cols


def test_windowed_spans_in_the_trace(tmp_path):
    t, _ = _table()
    m = BatchMiner(SIZES)
    m.mine_windowed(t, window_budget=128)
    spans = _host_spans(tmp_path,
                        lambda: m.mine_windowed(t, window_budget=128))
    windows = -(-len(t) // 128)
    count = {}
    for name, *_ in spans:
        count[name] = count.get(name, 0) + 1
    assert count == {"repro.stage1_sort": 1,
                     "repro.window.stage1_scan": windows * len(SIZES),
                     "repro.window.stage2_mix": windows,
                     "repro.window.stage3_sort": windows}


# -- named scopes in the compiled program ------------------------------------

#: instructions that do no work of their own
TRIVIAL = {"parameter", "constant", "tuple", "get-tuple-element", "copy"}
#: what XLA's CPU reduce-window rewriter builds a cumulative scan's tree
#: from; its fusions carry no op_name of the program's
REWRITER = {"parameter", "constant", "reduce-window", "slice", "bitcast",
            "pad", "broadcast", "add", "maximum", "minimum"}


def _computations(text: str):
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) .*\{\s*$", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and line.strip():
            comps[cur].append(line.strip())
    return comps, entry


def _executed(comps, entry) -> set:
    """The entry computation and the bodies of its control flow (not
    fused computations, reducers or comparators)."""
    seen, todo = set(), [entry]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ins in comps[c]:
            todo += re.findall(r"(?:body|condition|true_computation|"
                               r"false_computation)=%([\w.\-]+)", ins)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", ins):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def _opcode(ins: str) -> str:
    return re.match(r"(?:ROOT )?%\S+ = .*? ([a-z][\w-]*)\(", ins).group(1)


@pytest.mark.parametrize("variant", ["prime", "noac"])
def test_every_operation_under_one_stage_scope(variant):
    t, v = _table()
    if variant == "prime":
        m, kw = BatchMiner(SIZES), {}
    else:
        m = NOACMiner(SIZES, delta=1.0)
        kw = {"values": jnp.asarray(v), "value_domain": m.value_domain(v)}
    text = m._fn.lower(jnp.asarray(t), m._lo, m._hi, **kw).compile().as_text()
    comps, entry = _computations(text)
    seen = {s: 0 for s in STAGE_SCOPES}
    searches = 0
    for comp in _executed(comps, entry):
        for ins in comps[comp]:
            op = _opcode(ins)
            if op in TRIVIAL:
                continue
            name = re.search(r'op_name="([^"]*)"', ins)
            if name is None:
                assert op == "fusion", ins
                callee, = re.findall(r"calls=%([\w.\-]+)", ins)
                assert {_opcode(x) for x in comps[callee]} <= REWRITER, ins
                continue
            path = name.group(1).split("/")
            stages = [s for s in STAGE_SCOPES if s in path]
            assert len(stages) == 1, ins
            seen[stages[0]] += 1
            if "delta_search" in path:
                assert stages == ["stage2_components"], ins
                searches += 1
    assert all(seen.values()), seen
    assert (searches > 0) == (variant == "noac")
