"""CLI driver tests: the tricluster and cluster-serve mains and the
compile-cache setting they share."""
import contextlib
import functools
import io
import os
import re

import pytest

from repro.core import available_engines
from repro.launch import cluster_serve as cs_mod
from repro.launch import compile_cache as cc_mod
from repro.launch import tricluster as tri_mod


#: The driver's flags of each variant on the IMDB-like context; NOAC
#: filters by ρ_min, so that it keeps fewer clusters than prime does.
VARIANT_ARGS = {"prime": [], "noac": ["--delta", "1", "--rho-min", "0.3"]}


def _tricluster(*argv) -> str:
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert tri_mod.main(["--dataset", "imdb", *argv]) == 0
    return out.getvalue()


def _kept(out: str, variant: str) -> int:
    word = "triclusters" if variant == "noac" else "unique clusters"
    return int(re.search(rf"(\d+) {word};", out).group(1))


@functools.lru_cache(None)
def _reference_kept(variant: str) -> int:
    return _kept(_tricluster("--backend", "reference", "--variant", variant,
                             *VARIANT_ARGS[variant]), variant)


@pytest.mark.parametrize("backend,variant", [
    e for e in available_engines() if e[0] != "reference"])
def test_tricluster_engine_matches_reference(backend, variant):
    """Every registered (backend, variant) pair through the driver keeps
    as many clusters on the IMDB-like context as ``--backend reference``,
    and prints its top pattern where the engine materialises clusters."""
    out = _tricluster("--backend", backend, "--variant", variant,
                      "--print-top", "1", *VARIANT_ARGS[variant])
    assert _kept(out, variant) == _reference_kept(variant) > 0
    patterns = [ln for ln in out.splitlines() if ln.startswith("{")]
    assert bool(patterns) == (backend != "distributed")


@pytest.mark.parametrize("backend,rc", [("tpu", 2), ("cpu", 0)])
def test_cluster_serve_one_writer_per_chip(monkeypatch, capsys, backend, rc):
    """Several JAX writers cannot share a chip: on a TPU host a
    multi-shard plane is refused before any process starts."""
    started = []
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(cs_mod, "_jax_backend_probe", lambda: backend)
    monkeypatch.setattr(cs_mod, "_serve_topology",
                        lambda args: started.append(args.shards) or 0)
    assert cs_mod.main(["--shards", "2"]) == rc
    assert started == ([] if rc else [2])
    if rc:
        assert "ROADMAP R1" in capsys.readouterr().err


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    import jax
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = cc_mod.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == (str(tmp_path) if from_env
                    else os.path.join(root, ".jax_cache"))
