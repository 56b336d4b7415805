"""Distributed (shard_map) engine: 1-device in-process parity + 8-device
subprocess parity (real collectives on a forced host mesh), one case per
check of the subprocess."""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

import _distributed_check as DC
from repro.core import BatchMiner, DistributedMiner, pad_tuples
from repro.data import synthetic
from repro.launch.mesh import make_local_mesh, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("strategy", ["replicate", "shuffle"])
def test_single_device_parity(strategy):
    mesh = make_mesh((1,), ("data",))
    ctx = synthetic.random_context((8, 6, 5), 96, seed=0)
    bm = BatchMiner(ctx.sizes)
    dm = DistributedMiner(ctx.sizes, mesh, axes="data", strategy=strategy)
    want, got = bm(ctx.tuples), dm(ctx.tuples)
    assert int(got.overflow) == 0
    np.testing.assert_array_equal(np.asarray(got.sig_lo),
                                  np.asarray(want.sig_lo))
    np.testing.assert_array_equal(np.asarray(got.gen_count),
                                  np.asarray(want.gen_count))
    np.testing.assert_allclose(np.asarray(got.density),
                               np.asarray(want.density), rtol=1e-6)


@pytest.fixture(scope="module")
def multidevice_run():
    """One run of every check on a real 8-device mesh (pod×data too), in
    a separate process so the main test process keeps its single-device
    view: the process and its verdicts by check name."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_distributed_check.py")],
        capture_output=True, text=True, env=env, timeout=900)
    verdicts = [json.loads(line) for line in proc.stdout.splitlines()
                if line.startswith("{")]
    return proc, {v["check"]: v for v in verdicts}


@pytest.mark.parametrize("check", DC.CHECK_NAMES)
def test_multidevice_subprocess(multidevice_run, check):
    proc, verdicts = multidevice_run
    assert check in verdicts, \
        f"no verdict (rc {proc.returncode}):\n{proc.stderr[-4000:]}"
    assert verdicts[check]["ok"], verdicts[check]["error"]


def test_multidevice_verdict_per_check(monkeypatch, capsys):
    """The subprocess body reports every check on a line of its own, a
    failing one with its traceback, and goes on to the next."""
    def fails(mesh, why):
        raise AssertionError(why)
    monkeypatch.setattr(DC, "MESHES", {"none": lambda: None})
    monkeypatch.setattr(DC, "CHECKS", [
        ("passes", lambda mesh: None, "none", (), {}),
        ("fails", fails, "none", ("planted",), {}),
        ("after", lambda mesh: None, "none", (), {})])
    assert DC.main() == 1
    lines = capsys.readouterr().out.splitlines()
    verdicts = [json.loads(line) for line in lines]
    assert [(v["check"], v["ok"]) for v in verdicts] == [
        ("passes", True), ("fails", False), ("after", True)]
    assert "AssertionError: planted" in verdicts[1]["error"]


def test_local_mesh_spans_every_device():
    mesh = make_local_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (len(jax.devices()), 1)


def test_padding_is_idempotent():
    ctx = synthetic.random_context((7, 7, 7), 61, seed=1)
    padded = pad_tuples(ctx.tuples, 8)
    assert padded.shape[0] == 64
    bm = BatchMiner(ctx.sizes)
    a, b = bm(ctx.tuples), bm(padded)
    assert int(np.asarray(a.is_unique).sum()) == int(
        np.asarray(b.is_unique).sum())


def _series(obs, name, field="value") -> dict:
    doc = obs.metrics.to_dict().get(name, {"series": []})
    return {tuple(sorted(r["labels"].items())): r[field]
            for r in doc["series"]}


def test_shuffle_counts_and_phases_with_a_hub():
    """An enabled hub counts the records routed and the owner slots
    sorted per mode, the overflow retry, the δ-window path of each
    owner stage, and times the host phases under the batch path's
    names; a retried mine is still the batch answer."""
    from repro.core import NOACMiner
    from repro.obs import Obs
    mesh = make_mesh((1,), ("data",))
    ctx = synthetic.random_context((8, 6, 5), 96, seed=2,
                                   values=True).deduplicated()
    t = ctx.num_tuples
    obs = Obs.create()
    dm = DistributedMiner(ctx.sizes, mesh, axes="data", strategy="shuffle",
                          delta=1.0, capacity_factor=0.5, obs=obs)
    got = dm(ctx.tuples, ctx.values)
    want = NOACMiner(ctx.sizes, delta=1.0)(ctx.tuples, ctx.values)
    for f in ("sig_lo", "sig_hi", "keep", "density"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)))
    assert dm.capacity_factor == 1.0
    assert _series(obs, "distributed_shuffle_retries_total") == {(): 1}
    slots = -(-t // 2) + t
    for k in range(3):
        mode = (("mode", str(k)),)
        assert _series(obs, "distributed_shuffle_records_total")[mode] \
            == 2 * t
        assert _series(obs, "distributed_shuffle_slots_total")[mode] \
            == slots
    paths = _series(obs, "pipeline_delta_bounds_total")
    assert sum(paths.values()) == 6 and len(paths) == 1
    stages = {dict(k)["stage"]: v for k, v in
              _series(obs, "pipeline_stage_ms", "count").items()}
    assert stages == {"mine.value_domain": 1, "mine.copy_in": 1,
                      "mine.dispatch": 2, "mine.overflow_check": 2}


def test_value_domain_read_from_the_host_array(monkeypatch):
    """The NOAC value domain comes from the caller's array before the
    copy-in: ``value_domain_host`` never sees a device array."""
    from repro.core import keys as K
    seen = []
    real = K.value_domain_host

    def spy(values):
        seen.append(type(values))
        return real(values)
    monkeypatch.setattr(K, "value_domain_host", spy)
    mesh = make_mesh((1,), ("data",))
    ctx = synthetic.random_context((8, 6, 5), 64, seed=3,
                                   values=True).deduplicated()
    dm = DistributedMiner(ctx.sizes, mesh, axes="data", strategy="shuffle",
                          delta=1.0)
    dm(ctx.tuples, ctx.values)
    assert seen == [np.ndarray]
