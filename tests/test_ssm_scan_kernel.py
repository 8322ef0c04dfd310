"""The Mamba-2 scan's Pallas kernels (``ops/ssm_scan.py``) on the CPU tier:
forward and the five gradients under the interpreter against ``ssd_chunked``
and against the recurrence a step at a time; ``shapes_ok`` against the TPU
compiler's own answer on both sides (``tests/mosaic_aot.py``); what the
mixer's dispatch selects.  Parity on the chip is ``chip_smoke.py``'s."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.nn.layers.ssm import Mamba2Mixer, ssd_chunked
from analytics_zoo_tpu.ops import dispatch
from tests.test_ssm import PAIRS, _inputs, _recurrence

scan = importlib.import_module("analytics_zoo_tpu.ops.ssm_scan")

NAMES = ("x", "dt", "a", "b", "c")
# the cell: granite-4.0-h-micro's mixer on 2 x 4,096 tokens
CELL = dict(bsz=2, l=4096, h=64, p=64, g=1, n=128, chunk=256,
            dtype=jnp.bfloat16)


@pytest.fixture
def exact_products():
    """The oracles' float32 products exact; not for the compiles: Mosaic
    takes no float32 precision on bfloat16 operands."""
    with jax.default_matmul_precision("highest"):
        yield


def _cast(args, dtype):
    x, dt, a, b, c = args
    return x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype)


def _as_float32(args):
    """The operands as the kernel sees them, in float32: what rounding the
    inputs alone costs is not the kernel's."""
    return _cast(_cast(args, jnp.bfloat16), jnp.float32)


def _close(got, want, exact: bool, name=""):
    """float32: to rounding.  bfloat16 operands: within the rounding of
    the products' operands (``tests/test_ssm.py``'s measure)."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert np.isfinite(got).all(), name
    scale = float(np.max(np.abs(want)))
    if exact:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * scale,
                                   err_msg=name)
    else:
        assert float(np.max(np.abs(got - want))) < 0.05 * scale, name


CASES = [pytest.param(l, chunk, decay, dtype, groups,
                      id=f"{l}-{chunk}-{decay}-{jnp.dtype(dtype).name}-g{groups}")
         for l, chunk in PAIRS for decay in ("weak", "strong")
         for dtype in (jnp.float32, jnp.bfloat16) for groups in (1, 2)]


@pytest.mark.usefixtures("exact_products")
@pytest.mark.parametrize("l,chunk,decay,dtype,groups", CASES)
def test_forward_is_the_chunked_scan_and_the_recurrence(l, chunk, decay,
                                                        dtype, groups):
    args = _inputs(l, decay, groups, seed=3)
    exact = dtype == jnp.float32
    got = jax.jit(lambda *s: scan.ssm_scan(*s, chunk, True))(
        *_cast(args, dtype))
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    _close(got, ssd_chunked(*_cast(args, dtype), chunk), exact)
    _close(got, _recurrence(*(args if exact else _as_float32(args))), exact)


@pytest.mark.usefixtures("exact_products")
@pytest.mark.parametrize("l,chunk,decay,dtype,groups", CASES)
def test_five_gradients_are_the_chunked_scans_and_the_recurrences(
        l, chunk, decay, dtype, groups):
    args = _inputs(l, decay, groups, seed=1)
    exact = dtype == jnp.float32
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def grads(f, args):
        return jax.jit(jax.grad(lambda *s: jnp.sum(w * f(*s)),
                                argnums=range(5)))(*args)

    got = grads(lambda *s: scan.ssm_scan(*s, chunk, True),
                _cast(args, dtype))
    chunked = grads(lambda *s: ssd_chunked(*s, chunk), _cast(args, dtype))
    by_steps = grads(_recurrence, args if exact else _as_float32(args))
    for name, g, r, s, arg in zip(NAMES, got, chunked, by_steps,
                                  _cast(args, dtype)):
        assert g.shape == arg.shape and g.dtype == arg.dtype, name
        _close(g, r, exact, name)
        _close(g, s, exact, name)


@pytest.mark.usefixtures("exact_products")
def test_two_heads_of_64_share_their_lanes():
    """The cell's head size at a tiny length: heads taken two at a time,
    two head blocks a group, the second group's B and C."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    bsz, l, h, p, g, n, chunk = 1, 32, 64, 64, 2, 16, 16
    x = jax.random.normal(ks[0], (bsz, l, h, p))
    b, c = (jax.random.normal(k, (bsz, l, g, n)) for k in ks[1:3])
    dt = jax.random.uniform(ks[3], (bsz, l, h), minval=0.001, maxval=2.5)
    a = -jax.random.uniform(ks[4], (h,), minval=1.0, maxval=16.0)
    args = (x, dt, a, b, c)
    assert scan._head_block(h // g) == 16
    assert scan._heads_a_lane_group(16, p) == 2
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def both(f):
        return jax.jit(jax.value_and_grad(
            lambda *s: (lambda y: (jnp.sum(w * y), y))(f(*s)),
            argnums=range(5), has_aux=True))(*args)

    (_, y), got = both(lambda *s: scan.ssm_scan(*s, chunk, True))
    (_, y_want), want = both(lambda *s: ssd_chunked(*s, chunk))
    _close(y, y_want, True)
    for name, u, v in zip(NAMES, got, want):
        _close(u, v, True, name)


@pytest.mark.usefixtures("exact_products")
@pytest.mark.parametrize("decay", ["weak", "strong"])
def test_the_cells_chunk_of_256_over_two_chunks(decay):
    """The published chunk, whole tiles of lanes a side; two chunks, so
    the states carry."""
    x, dt, a, b, c = _inputs(512, decay, seed=6)
    # slow enough a decay that the far block still counts
    args = (x, dt / (40.0 if decay == "weak" else 1.0), a, b, c)
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def both(f):
        return jax.jit(jax.value_and_grad(
            lambda *s: (lambda y: (jnp.sum(w * y), y))(f(*s)),
            argnums=range(5), has_aux=True))(*args)

    (_, y), got = both(lambda *s: scan.ssm_scan(*s, 256, True))
    (_, y_want), want = both(lambda *s: ssd_chunked(*s, 256))
    _close(y, y_want, True)
    for name, u, v in zip(NAMES, got, want):
        if decay == "strong":
            # log-decays sum to thousands over 256 steps: float32 leaves
            # the gradient of A 1e-3 of its size from the float64
            # recurrence, in the kernel and in ``ssd_chunked`` alike
            assert float(jnp.max(jnp.abs(u - v))) < 3e-3 * float(
                jnp.max(jnp.abs(v))), name
        else:
            _close(u, v, True, name)


def _specs(bsz, l, h, p, g, n, chunk, dtype):
    from tests.mosaic_aot import spec

    group = spec((bsz, l, g, n), dtype)
    return (spec((bsz, l, h, p), dtype), spec((bsz, l, h), jnp.float32),
            spec((h,), jnp.float32), group, group)


def _compile_both(bsz, l, h, p, g, n, chunk, dtype):
    """The forward with its residual and the backward, through the TPU's
    compiler; the kernels' names in the compiled programs."""
    from tests.mosaic_aot import spec, tpu_compile

    args = _specs(bsz, l, h, p, g, n, chunk, dtype)
    fwd = tpu_compile(lambda *s: scan._scan_fwd(*s, chunk, False,
                                                with_states=True), *args)
    bwd = tpu_compile(
        lambda *s: scan._scan_bwd(*s, chunk, False), *args,
        spec((bsz, l // chunk, h * p, n), jnp.float32),
        spec((bsz, l, h, p), jnp.float32))
    assert "ssm_scan_fwd" in fwd.as_text()
    assert "ssm_scan_bwd" in bwd.as_text()


def _ok(bsz, l, h, p, g, n, chunk, dtype):
    return scan.shapes_ok((bsz, l, h, p), g, n, chunk, dtype)


class TestShapesOkIsTheCompilersAnswer:
    """Both sides of every clause of ``shapes_ok``: what it admits Mosaic
    compiles, forward and backward; what it refuses Mosaic refuses."""

    @pytest.mark.parametrize("shape", [
        CELL,
        dict(CELL, l=512, dtype=jnp.float32),
        dict(CELL, l=512, h=16, g=2),                 # two groups of 8
        dict(CELL, l=512, h=8, p=128),                # a head a lane group
        dict(CELL, l=512, h=4, p=32, n=200),          # four heads a group
        dict(CELL, l=64, h=16, chunk=16, dtype=jnp.float32),
    ], ids=["cell", "float32", "two-groups", "heads-of-128", "heads-of-32",
            "chunk-16"])
    def test_admitted_and_compiled(self, shape):
        assert _ok(**shape)
        _compile_both(**shape)

    @pytest.mark.parametrize("shape,why", [
        (dict(CELL, l=64, chunk=4), "divisible by 8 and 128"),
        (dict(CELL, l=512, h=16, g=2, n=64), "divisible by 8 and 128"),
        (dict(CELL, l=512, h=16, g=2, p=8), "divisible by 8 and 128"),
        (dict(CELL, l=512, dtype=jnp.float16), "Mosaic failed to compile"),
    ], ids=["chunk-4", "state-64-in-two-groups", "eight-heads-of-8-a-group",
            "float16"])
    def test_refused_by_both(self, shape, why):
        assert not _ok(**shape)
        with pytest.raises(Exception, match=why):
            _compile_both(**shape)

    def test_a_ragged_length_or_group_is_refused_before_any_compile(self):
        assert not _ok(**dict(CELL, l=4000))
        assert not _ok(**dict(CELL, g=3))


class TestTheMixersDispatch:
    def _series(self, monkeypatch, **mixer):
        from tests.mosaic_aot import selected_series

        kw = dict(n_heads=64, head_dim=64, d_state=128, n_groups=1,
                  chunk_size=256)
        kw.update(mixer)
        mixer = Mamba2Mixer(2048, name="mixer_dispatch", **kw)
        h = mixer.n_heads
        return selected_series(
            monkeypatch,
            lambda xbc, dt: mixer._scan(
                {"dt_bias": jnp.zeros((h,)), "A_log": jnp.zeros((h,)),
                 "D": jnp.ones((h,))}, xbc, dt),
            jax.ShapeDtypeStruct((2, 4096, mixer.conv_dim), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, 4096, h), jnp.bfloat16))

    def test_pallas_at_the_cells_shape(self, monkeypatch):
        from tests.mosaic_aot import series

        assert self._series(monkeypatch) == series("ssm_scan", "pallas")

    def test_reference_at_a_refused_shape(self, monkeypatch):
        from tests.mosaic_aot import series

        assert self._series(monkeypatch, n_groups=2, d_state=64) == series(
            "ssm_scan", "reference")

    def test_reference_off_the_tpu(self):
        assert not dispatch.on_tpu()
        mixer = Mamba2Mixer(32, n_heads=4, head_dim=16, d_state=8,
                            chunk_size=8, name="mixer_off_tpu")
        u = jnp.zeros((1, 16, 32))
        p = mixer.build_params(jax.random.PRNGKey(1), u.shape)
        from analytics_zoo_tpu.observe.metrics import METRICS

        mark = METRICS.snapshot()
        jax.eval_shape(mixer.forward, p, u)
        assert METRICS.delta(mark)["counters"] == {
            'ops_kernel_selected_total{kernel="ssm_scan",path="reference"}':
                1}
