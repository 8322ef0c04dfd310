"""The comparison that decides ``correct`` has to come out false where it
should: for the control (the reference put in the program's place and
computed in fp8, the precision below the configuration's), and for each
fault a training cell can have, planted underneath a run that is otherwise
driven as ``run.py`` drives it (only its look for a chip is skipped)."""

import time

import numpy as np
import pytest

import tiny


@pytest.mark.parametrize("cell_name", ["bert-tiny-fit", "resnet50-tiny-fit"])
def test_control_in_fp8_comes_out_not_correct(harness, tree, monkeypatch,
                                              cell_name):
    _, spec = harness
    tiny.only_chips(monkeypatch, 1)
    from harness import compare
    from harness.drivers.fit import Session

    cell = spec.load_cell(tree, cell_name)
    s = Session(cell, 31, time.perf_counter())
    s.load_rows(compare.STEPS * s.batch)
    want = s.reference()
    again = compare.numbers(s.reference(), want)
    ok, _ = compare.judge(again, cell.limits)
    assert ok, "the reference agrees with itself"
    control = compare.numbers(s.reference(quant=compare.fp8_round,
                                          act=compare.fp8_round), want)
    ok, checks = compare.judge(control, cell.limits)
    assert not ok, checks
    if cell_name.startswith("bert"):    # one more compile; the light model
        half = compare.numbers(s.reference(rows=slice(0, s.batch // 2)),
                               want)
        ok, checks = compare.judge(half, cell.limits)
        assert not ok, checks


def _break_dispatch(monkeypatch, fault):
    from analytics_zoo_tpu.train.estimator import Estimator

    real = Estimator._dispatch_step

    def tiled(a, keep):
        import jax
        import jax.numpy as jnp

        return jax.device_put(
            jnp.concatenate([a[:keep]] * (a.shape[0] // keep), axis=0),
            a.sharding)

    def broken(self, kind, batch_x, batch_y, **kw):
        import jax.numpy as jnp

        n = batch_y.shape[0]
        if fault == "state_unchanged":
            # the step hands back what it was given
            self.global_step += 1
            return 1, jnp.zeros((), jnp.float32)
        # rows left out, the mean taken over the rest: half of the batch,
        # or all but the first chip's share (the exchange left out)
        keep = n // 2 if fault == "half_batch" else n // 4
        return real(self, kind, [tiled(a, keep) for a in batch_x],
                    tiled(batch_y, keep), **kw)

    monkeypatch.setattr(Estimator, "_dispatch_step", broken)


@pytest.mark.parametrize("cell_name,fault", [
    ("bert-tiny-fit", "state_unchanged"),
    ("bert-tiny-fit", "half_batch"),
    ("bert-tiny-dp4", "exchange_left_out"),
])
def test_a_broken_timed_path_comes_out_not_correct(harness, tree,
                                                   monkeypatch, cell_name,
                                                   fault):
    bench_run, spec = harness
    cell = spec.load_cell(tree, cell_name)
    tiny.only_chips(monkeypatch, cell.chips)
    _break_dispatch(monkeypatch, fault)
    out = bench_run.measure(cell, 99, 0.2, False, time.perf_counter())
    assert out["correct"] is False, (fault, out["checks"])
    over = [k for k, (v, lim) in out["checks"].items() if v > lim]
    assert over, out["checks"]
    if fault == "state_unchanged":
        # the gap of norms reads 1 where nothing moved
        assert out["checks"]["delta_norm"][0] == pytest.approx(1.0)


def test_fp8_round_keeps_three_mantissa_bits():
    import jax.numpy as jnp

    from harness import compare

    x = jnp.asarray([448.0, 1.0, 1.0625, 1.125, 0.3, -300.0], jnp.float32)
    got = np.asarray(compare.fp8_round(x))
    # largest magnitude maps to 448; the spacing in [1, 2) is 1/8, in
    # [256, 448] it is 32, in [0.25, 0.5) it is 1/32
    assert got.tolist() == [448.0, 1.0, 1.0, 1.125, 0.3125, -288.0]
    ints = jnp.asarray([1, 2, 3])
    assert compare.fp8_round(ints) is ints
