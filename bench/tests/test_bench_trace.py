"""The trace reduction and the roofline arithmetic, checked by hand on
a small trace recorded on the chip and on small tables."""
from __future__ import annotations

import json
import re

import pytest

from bench_testutil import BENCH

from benchlib import harness, trace, work

EXCERPT = BENCH / "data" / "trace_excerpt.json"


def test_busy_union_by_hand():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 20, 5], ["d", 21, 1]]
    assert trace.intervals_union(ev) == [[0.0, 15.0], [20.0, 25.0]]
    assert trace.busy_ns(ev) == 20.0
    assert trace.op_seconds(ev, ("^a$", "^c$")) == 15e-9


def test_summary_by_hand():
    tr = {"devices": {"0": [["op.x", 0, 2e9], ["op.y", 3e9, 1e9]],
                      "1": [["op.x", 0, 1e9]]},
          "host": [["bench.mine", 0, 4e9], ["bench.fetch", 2.2e9, 0.5e9]]}
    s = trace.summarize(tr, 5.0)
    assert s["busy_s"] == pytest.approx(2.0)
    assert s["device_ops"][0] == ["op.x", 1.5]
    assert s["idle_gaps"] == [["bench.fetch", 1.0]]
    assert trace.idle_share_percent(s) == pytest.approx(60.0)


@pytest.fixture(scope="module")
def excerpt():
    with open(EXCERPT) as f:
        return json.load(f)


def test_recorded_trace_reduces(excerpt):
    window = excerpt["window_s"]
    s = trace.summarize(excerpt, window)
    assert 0 < s["busy_s"] <= window
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    assert all(g[0].startswith("bench.") or g[0] == "outside bench spans"
               for g in s["idle_gaps"])
    assert s["busy_s"] == pytest.approx(excerpt["busy_s"], rel=1e-9)


@pytest.mark.parametrize("metric", ["radix_roofline.mine",
                                    "segment_reduce_roofline.mine"])
def test_recorded_trace_names_the_kernels(excerpt, metric):
    """The kernels show up under the names the readers look for, and the
    shares read from the excerpt are inside (0, 100]."""
    mod = harness.load_module("metrics", metric)
    dev = excerpt["devices"][sorted(excerpt["devices"])[0]]
    assert trace.op_seconds(dev, mod.KERNELS) > 0
    s = trace.summarize(excerpt, excerpt["window_s"])
    share = mod.read(s, excerpt["facts"], harness.load_peaks("TPU v5 lite"))
    assert 0 < share <= 100


@pytest.mark.parametrize("metric", ["radix_roofline.mine",
                                    "segment_reduce_roofline.mine",
                                    "gather_ms_per_mine.mine",
                                    "scatter_ms_per_mine.mine"])
def test_readers_return_nothing_without_events(metric):
    s = trace.summarize({"devices": {"0": [["fusion", 0, 10]]}, "host": []},
                        1.0)
    facts = {"mines": 3, "rows": 10, "radix_bytes_per_mine": 1,
             "segment_reduce_bytes_per_mine": 1}
    peaks = harness.load_peaks("TPU v5 lite")
    assert harness.load_module("metrics", metric).read(s, facts, peaks) is None


#: how the chip's trace names a gather of a table column through a
#: permutation, and a scatter of ranks into one
GATHER_OP = ('%fusion.85 = u32[816197]{0:T(1024)S(1)} fusion(u32[816197]'
             '{0:T(1024)} %copy-done.13, s32[817152]{0:T(1024)S(1)} '
             '%copy-done.57), kind=kCustom, calls=%fused_computation.85')
SCATTER_OP = ('%fusion.7 = s32[816197]{0:T(1024)} fusion(s32[816197]'
            '{0:T(1024)} %a, s32[816197]{0:T(1024)} %b, s32[]{:T(128)} '
            '%c), kind=kCustom, calls=%fused_computation.7')


def test_gather_time_by_hand():
    mod = harness.load_module("metrics", "gather_ms_per_mine.mine")
    s = {"devices": {"0": [[GATHER_OP, 0, 3e6], [SCATTER_OP, 0, 5e6],
                           [GATHER_OP.replace("816197]{0:T(1024)S", "816]"
                                              "{0:T(1024)S", 1), 0, 7e6]]}}
    # two mines, 3 ms of gathers of 816,197-row columns between them
    assert mod.read(s, {"mines": 2, "rows": 816197}, {}) == \
        pytest.approx(1.5)


def test_scatter_time_by_hand():
    mod = harness.load_module("metrics", "scatter_ms_per_mine.mine")
    s = {"devices": {"0": [[GATHER_OP, 0, 3e6], [SCATTER_OP, 0, 5e6]]}}
    assert mod.read(s, {"mines": 2, "rows": 816197}, {}) == \
        pytest.approx(2.5)


def test_recorded_trace_gathers_and_scatters(excerpt):
    """The gathers and scatters of two BibSonomy mines are most of
    their device time (the compiled program's HLO names 93 gathers and
    30 scatters of the table's length a mine)."""
    s = trace.summarize(excerpt, excerpt["window_s"])
    ms = {m: harness.load_module("metrics", m).read(s, excerpt["facts"], {})
          for m in ("gather_ms_per_mine.mine", "scatter_ms_per_mine.mine")}
    per_mine = 1e3 * excerpt["busy_s"] / excerpt["facts"]["mines"]
    assert 0.5 * per_mine < ms["gather_ms_per_mine.mine"] < per_mine
    assert 0.1 * per_mine < ms["scatter_ms_per_mine.mine"] < 0.3 * per_mine
    assert sum(ms.values()) < per_mine
    dev = excerpt["devices"]["0"]
    for m, n in (("gather_ms_per_mine.mine", 93),
                 ("scatter_ms_per_mine.mine", 30)):
        pat = re.compile(harness.load_module("metrics", m).pattern(816197))
        assert sum(1 for e in dev if pat.search(e[0])) == 2 * n


#: how the chip's trace names one rank pass of the radix sort
RANK_OP = ('%_unknown_.50 = s32[817152]{0:T(1024)S(1)} custom-call('
           'u32[817152]{0:T(1024)S(1)} %pad.24, s32[256]{0:T(256)S(1)} '
           '%pad_add_fusion.42), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={u32[817152]{0}, s32[256]{0}}')


def test_roofline_by_hand():
    s = {"devices": {"0": [[RANK_OP, 0, 1000], ["%fusion.1 = s32[8]", 0, 9]]},
         "busy_s": 1e-6, "window_s": 1.0}
    facts = {"mines": 2, "radix_bytes_per_mine": 819}
    share = harness.load_module("metrics", "radix_roofline.mine").read(
        s, facts, {"hbm_bytes_per_s": 819e9})
    # 2 x 819 bytes at 819 GB/s take 2 ns of the kernel's 1000 ns
    assert share == pytest.approx(0.2)


def test_radix_bytes_by_hand():
    # 44 live bits: two words, six 8-bit passes
    hist = 2 * 4 * 1000 + 6 * 256 * 4
    rank = 6 * (2 * 4 * 1000 + 256 * 4)
    assert work.radix_sort_bytes(1000, 44) == hist + rank
    # 31 live bits: one word, four passes
    assert work.radix_sort_bytes(10, 31) == 4 * 10 + 4 * 1024 + 4 * (80 + 1024)
    assert work.segment_reduce_bytes_per_mine(1000, 3) == 3 * 24 * 1000


@pytest.mark.parametrize("sizes,slots", [((2337, 67464, 28920), None),
                                         ((6040, 3952, 5), 5),
                                         ((584, 16866, 7230), None)])
def test_key_plan_matches_program(sizes, slots):
    """The copied key arithmetic agrees with the program's plan today:
    live bits, words and 8-bit pass counts."""
    from repro.core import keys as K
    from repro.core import radix as RX
    plan = K.plan_context_keys(sizes, with_values=slots is not None,
                               value_slots=slots)[0]
    bits = work.key_bits(sizes, slots)
    assert bits == plan.total_bits
    assert (1 if bits <= 32 else 2) == plan.words
    assert RX.plan_radix(bits, 4096, digit_bits=8).passes == -(-bits // 8)
    t = 1000
    assert work.radix_bytes_per_mine(t, sizes, slots) == (
        len(sizes) * work.radix_sort_bytes(t, bits)
        + work.radix_sort_bytes(t, 64))
