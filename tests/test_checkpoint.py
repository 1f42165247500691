"""Checkpoint store: atomicity, corruption recovery, retention, async save,
and the fault-tolerant Trainer (failure injection -> restore -> exact replay)."""

import json
import os
import shutil

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.store import CheckpointStore
from repro.configs import get_config
from repro.data.synthetic import SyntheticLM
from repro.launch.mesh import auto_mesh
from repro.models import LOCAL, init
from repro.train.loop import InjectedFailure, Trainer, make_train_step
from repro.train.optimizer import adamw


def _tree(seed=0):
    k = jax.random.key(seed)
    return {"a": jax.random.normal(k, (4, 5)),
            "nested": {"b": jnp.arange(7), "c": (jnp.ones(3), jnp.zeros(2))}}


def test_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path))
    t = _tree()
    store.save(3, t)
    step, got = store.load_latest()
    assert step == 3
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b), t, got)
    # tuple structure preserved
    assert isinstance(got["nested"]["c"], tuple)


def test_latest_and_retention(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        store.save(s, _tree(s))
    assert store.all_steps() == [3, 4]
    step, got = store.load_latest()
    assert step == 4


def test_corrupt_checkpoint_skipped(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=5)
    store.save(1, _tree(1))
    store.save(2, _tree(2))
    # corrupt the newest
    path = os.path.join(str(tmp_path), "step_00000002", "leaf_0000.npy")
    with open(path, "wb") as f:
        f.write(b"garbage")
    step, got = store.load_latest()
    assert step == 1


def test_async_save(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.save(7, _tree(7), async_=True)
    store.wait()
    assert store.all_steps() == [7]


def test_trainer_failure_injection_recovers(tmp_path):
    """Crash at step 7 (after checkpoint at 5) -> restore -> identical final
    params to an uninterrupted run (data is a pure function of step)."""
    cfg = get_config("paper-mlp").reduced(
        d_model=32, d_ff=64, n_layers=1, vocab_size=32, n_heads=2,
        n_kv_heads=2, head_dim=16)
    opt = adamw(lr=1e-3)
    step_fn = make_train_step(cfg, opt, LOCAL, remat="none", donate=False)
    ds = SyntheticLM(cfg.vocab_size, 16, 4, seed=0)

    def data(step):
        tb = ds.batch(step)
        return {"tokens": tb.tokens, "targets": tb.targets,
                "loss_mask": tb.loss_mask}

    crashed = {"done": False}

    def injector(step):
        if step == 7 and not crashed["done"]:
            crashed["done"] = True
            raise InjectedFailure("simulated node failure")

    t1 = Trainer(cfg, opt, data, step_fn, str(tmp_path / "a"), save_every=5,
                 failure_injector=injector)
    params_a, _ = t1.run(10)
    assert crashed["done"]

    t2 = Trainer(cfg, opt, data, step_fn, str(tmp_path / "b"), save_every=5)
    params_b, _ = t2.run(10)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     params_a, params_b)
    assert max(jax.tree.leaves(d)) < 1e-6


def test_elastic_restore_resharding(tmp_path):
    """A checkpoint saved unsharded restores onto a (1,1) mesh sharding —
    the mechanism behind elastic rescale (device_put at load)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    store = CheckpointStore(str(tmp_path))
    t = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    store.save(1, t)
    mesh = auto_mesh((1, 1), ("data", "model"))
    sh = {"w": NamedSharding(mesh, P("data", "model"))}
    step, got = store.load_latest(shardings=sh)
    assert got["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(t["w"]))


def test_straggler_monitor():
    from repro.train.loop import StragglerMonitor
    m = StragglerMonitor(factor=3.0)
    for i in range(10):
        m.record(i, 1.0)
    assert not m.events
    assert m.record(10, 10.0)
    assert m.events and m.events[0][0] == 10
