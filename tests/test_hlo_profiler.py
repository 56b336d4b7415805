"""Scan-aware HLO profiler: unit tests on synthetic HLO text + a live
check that while-body FLOPs are multiplied by the trip count."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo import parse_module, profile_module


_SYNTHETIC = """\
HloModule test

%fused_dus (p0: f32[8,16], p1: f32[1,16], p2: s32[]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p1 = f32[1,16]{1,0} parameter(1)
  %p2 = s32[] parameter(2)
  ROOT %dus = f32[8,16]{1,0} dynamic-update-slice(%p0, %p1, %p2, %p2)
}

%body (arg: (s32[], f32[16,16], f32[8,16])) -> (s32[], f32[16,16], f32[8,16]) {
  %arg = (s32[], f32[16,16]{1,0}, f32[8,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[16,16]{1,0} get-tuple-element(%arg), index=1
  %acc = f32[8,16]{1,0} get-tuple-element(%arg), index=2
  %dot = f32[16,16]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %row = f32[1,16]{1,0} bitcast(%dot)
  %upd = f32[8,16]{1,0} fusion(%acc, %row, %i), kind=kLoop, calls=%fused_dus
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %out = (s32[], f32[16,16]{1,0}, f32[8,16]{1,0}) tuple(%ip, %dot, %upd)
}

%cond (arg: (s32[], f32[16,16], f32[8,16])) -> pred[] {
  %arg = (s32[], f32[16,16]{1,0}, f32[8,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(8)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[16,16], b: f32[8,16]) -> (s32[], f32[16,16], f32[8,16]) {
  %a = f32[16,16]{1,0} parameter(0)
  %b = f32[8,16]{1,0} parameter(1)
  %zero = s32[] constant(0)
  %init = (s32[], f32[16,16]{1,0}, f32[8,16]{1,0}) tuple(%zero, %a, %b)
  ROOT %w = (s32[], f32[16,16]{1,0}, f32[8,16]{1,0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"8"}}
}
"""


def test_parse_module_structure():
    comps, entry = parse_module(_SYNTHETIC)
    assert entry == "main"
    assert set(comps) == {"fused_dus", "body", "cond", "main"}
    assert comps["body"].instrs["%dot"].opcode == "dot"


def test_trip_count_scaling_and_dus_accounting():
    prof = profile_module(_SYNTHETIC, 1)
    # dot: 2*16*16*16 flops, executed 8 times
    assert prof.mxu_flops == 8 * 2 * 16 * 16 * 16
    assert prof.trip_counts.get("body") == 8
    # DUS fusion writes one 64-byte row per iteration, not the 512B buffer
    assert prof.traffic_bytes < 8 * (3 * 16 * 16 * 4) * 2


def test_live_scan_flops_counted_per_trip():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    n_layers, d = 12, 32
    comp = jax.jit(jax.grad(f)).lower(
        jax.ShapeDtypeStruct((d, d), jnp.float32),
        jax.ShapeDtypeStruct((n_layers, d, d), jnp.float32)).compile()
    prof = profile_module(comp.as_text(), 1)
    # fwd dot + dx dot per layer (grad wrt x only)
    want = 2 * n_layers * 2 * d ** 3
    assert abs(prof.mxu_flops - want) / want < 0.05
    raw = comp.cost_analysis()["flops"]
    assert prof.mxu_flops > 4 * raw   # XLA counted the body once

