"""CLI driver smoke tests: tricluster / train / serve mains."""
import json
import os

import pytest

from repro.launch import cluster_serve as cs_mod
from repro.launch import compile_cache as cc_mod
from repro.launch import serve as serve_mod
from repro.launch import train as train_mod
from repro.launch import tricluster as tri_mod


def test_tricluster_batch_imdb(capsys):
    assert tri_mod.main(["--dataset", "imdb", "--backend", "batch",
                         "--print-top", "1"]) == 0
    out = capsys.readouterr().out
    assert "unique clusters" in out


def test_tricluster_reference_and_noac(capsys):
    assert tri_mod.main(["--dataset", "random", "--n-tuples", "256",
                         "--backend", "reference"]) == 0
    assert tri_mod.main(["--dataset", "frames", "--n-tuples", "512",
                         "--delta", "100", "--rho-min", "0.5"]) == 0
    out = capsys.readouterr().out
    assert "NOAC" in out


def test_tricluster_streaming(capsys):
    assert tri_mod.main(["--dataset", "random", "--n-tuples", "512",
                         "--backend", "streaming", "--chunks", "4"]) == 0


def test_train_driver_with_resume(tmp_path, capsys):
    ckpt = str(tmp_path / "ckpt")
    metrics = str(tmp_path / "m.json")
    args = ["--arch", "h2o-danube-1.8b", "--smoke", "--steps", "6",
            "--global-batch", "2", "--seq", "32", "--ckpt-dir", ckpt,
            "--ckpt-every", "3", "--log-every", "2",
            "--metrics-out", metrics]
    assert train_mod.main(args) == 0
    rows = json.load(open(metrics))
    assert rows[-1]["step"] == 6
    # resume two more steps from the checkpoint
    args2 = [a if a != "6" else "8" for a in args] + ["--resume", "auto"]
    assert train_mod.main(args2) == 0
    out = capsys.readouterr().out
    assert "resumed from step" in out


def test_serve_driver(capsys):
    assert serve_mod.main(["--arch", "qwen3-0.6b", "--smoke",
                           "--batch", "2", "--prompt-len", "8",
                           "--new-tokens", "4", "--max-len", "32"]) == 0
    out = capsys.readouterr().out
    assert "tok/s" in out


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
