import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines: jax locks the device count on first init.
# The dry-run (and only the dry-run) needs 512 placeholder host devices so
# jax.make_mesh can build the production meshes (16,16) and (2,16,16).
# Tests run this file as a subprocess with REPRO_DRYRUN_DEVICES to shrink it.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from ..analysis.roofline import V5E, roofline_from_compiled
from ..configs import SHAPES, get_config, shape_applicable, ARCHS
from ..models.api import get_model, input_specs
from ..sharding.rules import MeshRules
from ..train.step import (TrainConfig, make_train_step, state_shardings,
                          state_structs)
from .mesh import make_production_mesh, mesh_name

"""Multi-pod dry-run driver (brief §MULTI-POD DRY-RUN).

For every (architecture × input shape × mesh) cell: build the production
mesh, lower the real jit'd step (train_step / prefill / decode_step — the
same function objects the drivers run), ``.compile()`` it, and record

  * ``compiled.memory_analysis()``  — proves the cell fits in HBM,
  * ``compiled.cost_analysis()``    — FLOPs / bytes for §Roofline,
  * the parsed collective schedule  — collective_bytes for §Roofline.

Failures (sharding mismatch, OOM at compile, unsupported collective) are
bugs in the framework, not in the cell. Results append to a JSONL so the
run is resumable per cell.
"""


def apply_overrides(cfg, overrides: dict):
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def serve_param_structs(cfg, model, rules):
    """bf16 weight structs for serve cells. Under ``cfg.fsdp`` every
    parameter's spec is ZeRO-extended over the data axes (``zero1_spec``)
    — GSPMD then all-gathers each layer's weights inside the scan on use
    (ZeRO-inference). Plain TP layout otherwise."""
    if not cfg.fsdp:
        return model.structs(cfg, rules, dtype=jnp.bfloat16)
    from jax.sharding import NamedSharding
    from ..models.params import map_defs
    from ..train.optim import zero1_spec

    def one(d):
        spec = zero1_spec(rules.spec(d.axes, d.shape), d.shape, rules)
        return jax.ShapeDtypeStruct(
            d.shape, jnp.bfloat16,
            sharding=NamedSharding(rules.mesh, spec))

    return map_defs(one, model.param_defs(cfg))


def lower_cell(cfg, shape, mesh, *, tc: TrainConfig = TrainConfig()):
    """Lower one cell; returns (lowered, aux_info)."""
    rules = MeshRules(mesh, fsdp=cfg.fsdp)
    model = get_model(cfg)
    if shape.kind == "train":
        step = make_train_step(cfg, rules, tc)
        sstructs = state_structs(cfg, rules, tc)
        batch = input_specs(cfg, shape, rules)
        shard = state_shardings(cfg, rules, tc)
        lowered = jax.jit(step, out_shardings=(shard, None),
                          donate_argnums=(0,)).lower(sstructs, batch)
        return lowered, {"inputs": "state+batch"}
    pstructs = serve_param_structs(cfg, model, rules)
    if shape.kind == "prefill":
        inputs = input_specs(cfg, shape, rules)

        def fn(p, i):
            return model.prefill(cfg, p, i, shape.seq_len, rules)

        lowered = jax.jit(fn).lower(pstructs, inputs)
        return lowered, {"inputs": "params+tokens"}
    # decode: one new token against a cache of seq_len
    cache = model.cache_structs(cfg, shape.global_batch, shape.seq_len,
                                rules, dtype=jnp.bfloat16)
    toks = input_specs(cfg, shape, rules)["tokens"]

    def fn(p, c, t):
        return model.decode_step(cfg, p, c, t, rules)

    lowered = jax.jit(fn, donate_argnums=(1,)).lower(pstructs, cache, toks)
    return lowered, {"inputs": "params+cache+token"}


def shape_defaults(cfg, shape) -> dict:
    """Per-shape-kind config defaults (fit-tuning; overridable via --set).

    * train: microbatch the global batch so per-device activations (the
      logits/loss region above all) stay inside HBM;
    * serve (prefill/decode) on >=8B-param archs: fsdp=True — bf16 weights
      additionally sharded over the data axes and gathered per layer
      inside the scan (ZeRO-inference); a 76B model is 9.5 GB/chip under
      16-way TP alone, which starves a 16 GB v5e once the KV cache lands.
    """
    out = {}
    if (shape.kind == "train" and cfg.microbatch == 1
            and shape.global_batch % 8 == 0):
        out["microbatch"] = 8
    if shape.kind in ("prefill", "decode") and cfg.n_params() >= 8e9:
        out["fsdp"] = True
    return out


def run_cell(arch: str, shape_name: str, mesh, mesh_label: str,
             overrides: dict = None, verbose: bool = True) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    base = shape_defaults(cfg, shape)
    base.update(overrides or {})
    cfg = apply_overrides(cfg, base)
    row = {"arch": arch, "shape": shape_name, "mesh": mesh_label,
           "n_devices": int(mesh.devices.size)}
    runs, why = shape_applicable(cfg, shape)
    if not runs:
        row.update(status="skip", reason=why)
        return row
    t0 = time.time()
    try:
        lowered, aux = lower_cell(cfg, shape, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        report = roofline_from_compiled(
            compiled, arch=arch, shape=shape, mesh_name=mesh_label,
            n_devices=int(mesh.devices.size), cfg=cfg)
        ma = compiled.memory_analysis()
        if verbose:
            print(f"  memory_analysis: arg={ma.argument_size_in_bytes / 1e9:.3f}GB "
                  f"out={ma.output_size_in_bytes / 1e9:.3f}GB "
                  f"temp={ma.temp_size_in_bytes / 1e9:.3f}GB "
                  f"(fits={report.fits})")
            ca = compiled.cost_analysis()
            print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
                  f"bytes={ca.get('bytes accessed', 0):.3e}")
            print(f"  {report.row()}")
        row.update(status="ok", lower_s=round(t_lower, 2),
                   compile_s=round(t_compile, 2), **report.to_dict())
    except Exception as e:  # a failure here is a framework bug
        row.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return row


def iter_cells(archs, shapes):
    for arch in archs:
        for shape in shapes:
            yield arch, shape


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id or comma list or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or comma list or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already in --out")
    ap.add_argument("--set", action="append", default=[],
                    metavar="K=V", help="ModelConfig overrides (perf knobs)")
    args = ap.parse_args(argv)

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = (list(SHAPES) if args.shape == "all"
              else args.shape.split(","))
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    done = set()
    if args.resume and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                if line.strip():
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("1pod", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("2pod", make_production_mesh(multi_pod=True)))

    n_ok = n_skip = n_err = 0
    with open(args.out, "a") as f:
        for label, mesh in meshes:
            for arch, shape in iter_cells(archs, shapes):
                if (arch, shape, label) in done:
                    continue
                print(f"[dryrun] {arch} × {shape} × {label} "
                      f"({mesh.devices.size} devices)", flush=True)
                row = run_cell(arch, shape, mesh, label, overrides)
                if overrides:
                    row["overrides"] = overrides
                f.write(json.dumps(row) + "\n")
                f.flush()
                st = row["status"]
                n_ok += st == "ok"
                n_skip += st == "skip"
                n_err += st == "error"
                if st == "error":
                    print(f"  ERROR {row['error']}", flush=True)
                elif st == "skip":
                    print(f"  {row['reason']}", flush=True)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skip, {n_err} error")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
