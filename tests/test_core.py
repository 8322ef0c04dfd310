"""Tests for core: config, context/mesh, triggers, summary writer."""

import os

import numpy as np
import pytest


def test_config_env_override(monkeypatch):
    from analytics_zoo_tpu.core.config import ZooConfig

    monkeypatch.setenv("ZOO_SEED", "7")
    monkeypatch.setenv("ZOO_LOG_LEVEL", "DEBUG")
    cfg = ZooConfig.from_env()
    assert cfg.seed == 7
    assert cfg.log_level == "DEBUG"
    cfg2 = cfg.replace(seed=9)
    assert cfg2.seed == 9 and cfg.seed == 7


def test_context_mesh_8_devices(zoo_ctx):
    assert zoo_ctx.num_devices == 8
    assert zoo_ctx.mesh.axis_names == ("data",)


def test_context_custom_mesh():
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.core.context import set_zoo_context

    ctx = init_zoo_context(mesh_shape=(4, 2), axis_names=("data", "model"))
    assert ctx.mesh.devices.shape == (4, 2)
    # restore default for other tests
    init_zoo_context()


def test_tpu_mesh_that_the_topology_cannot_carry_is_loud(monkeypatch):
    # make_mesh must not swallow a create_device_mesh error and reshape
    from jax.experimental import mesh_utils

    from analytics_zoo_tpu.core.context import make_mesh

    class FakeTpu:
        platform = "tpu"

    def refuse(shape, devices):
        raise ValueError(f"cannot place mesh {shape} on this topology")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    with pytest.raises(ValueError, match="cannot place mesh"):
        make_mesh([FakeTpu(), FakeTpu()], (2,), ("data",))


def test_data_sharding(zoo_ctx):
    import jax
    import jax.numpy as jnp

    x = np.arange(16.0).reshape(16, 1)
    sharded = jax.device_put(jnp.asarray(x), zoo_ctx.data_sharding(2))
    assert len(sharded.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(sharded), x)


def test_triggers():
    from analytics_zoo_tpu.core.triggers import (
        And, EveryEpoch, MaxEpoch, MaxIteration, MinLoss, Or,
        SeveralIteration, TriggerState)

    s = TriggerState(epoch=3, iteration=30, epoch_finished=True, loss=0.5)
    assert EveryEpoch()(s)
    assert MaxEpoch(3)(s) and not MaxEpoch(4)(s)
    assert SeveralIteration(10)(s) and not SeveralIteration(7)(s)
    assert MinLoss(0.6)(s) and not MinLoss(0.4)(s)
    assert (MaxEpoch(3) & MaxIteration(30))(s)
    assert (MaxEpoch(99) | MaxIteration(30))(s)
    assert not And(MaxEpoch(99), MaxIteration(30))(s)
    assert Or(MaxEpoch(99), MaxIteration(99))(s) is False


def test_summary_writer_roundtrip(tmp_path):
    from analytics_zoo_tpu.core.summary import SummaryWriter, read_scalars

    w = SummaryWriter(str(tmp_path))
    for step, val in [(1, 0.5), (2, 0.25), (3, 0.125)]:
        w.add_scalar("loss", val, step)
    w.add_scalar("acc", 0.9, 3)
    w.close()
    scalars = read_scalars(str(tmp_path), "loss")
    assert [s for s, _ in scalars] == [1, 2, 3]
    np.testing.assert_allclose([v for _, v in scalars], [0.5, 0.25, 0.125])
    assert read_scalars(str(tmp_path), "acc") == [(3, pytest.approx(0.9))]


def test_checkpoint_roundtrip(tmp_path):
    import jax.numpy as jnp

    from analytics_zoo_tpu.train import checkpoint as ckpt

    tree = {"a": {"w": jnp.ones((3, 2)), "b": jnp.zeros(2)},
            "meta": np.asarray(5)}
    path = str(tmp_path / "t.npz")
    ckpt.save_pytree(path, tree)
    back = ckpt.load_pytree(path)
    np.testing.assert_allclose(back["a"]["w"], np.ones((3, 2)))
    assert int(back["meta"]) == 5


def test_checkpoint_manager(tmp_path):
    from analytics_zoo_tpu.train.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=2)
    for step in [10, 20, 30]:
        mgr.save(step, {"x": np.full((2,), float(step))})
    assert mgr.all_steps() == [20, 30]  # gc keeps last 2
    step, tree = mgr.restore()
    assert step == 30
    np.testing.assert_allclose(tree["x"], [30.0, 30.0])
