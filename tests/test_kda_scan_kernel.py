"""The KDA scan's Pallas kernels (``ops/kda_scan.py``) on the CPU tier:
forward and the five cotangents under the interpreter against
``kda_chunked`` and against the recurrence a position at a time, at decays
strong enough that a chunk's decay overflows float32 when factored;
``shapes_ok`` against the TPU compiler's own answer on both sides
(``tests/mosaic_aot.py``); what a checkpoint that keeps the named residuals
spares the backward pass; what the mixer's dispatch selects, and the
mixer's padded length through the kernels.  Parity on the chip is
``chip_smoke.py``'s."""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from analytics_zoo_tpu.nn.layers.kda import (KDAMixer, kda_chunked,
                                             kda_recurrent)
from analytics_zoo_tpu.ops import dispatch

scan = importlib.import_module("analytics_zoo_tpu.ops.kda_scan")

NAMES = ("q", "k", "v", "g", "beta")
# the cell: kimi-linear-48b-a3b-instruct's KDA layer on 2 x 8,192 tokens
CELL = dict(bsz=2, l=8192, h=32, dk=128, dv=128, chunk=64, sub=16,
            dtype=jnp.bfloat16)
# a step's log-decay: -1.6 at the mixer's initialisation, and stronger
DECAYS = {"init": 1.6, "strong": 6.0}


@pytest.fixture
def exact_products():
    """The oracles' float32 products exact; not for the compiles: Mosaic
    takes no float32 precision on bfloat16 operands."""
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(l, decay, seed, bsz=2, h=4, dk=128, dv=128):
    """Unit-norm q and k, log-decays uniform in (-2 decay, 0) a channel:
    several chunks of 64, two head blocks, batch 2."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    return (unit(jax.random.normal(ks[0], (bsz, l, h, dk))),
            unit(jax.random.normal(ks[1], (bsz, l, h, dk))),
            jax.random.normal(ks[2], (bsz, l, h, dv)),
            -2 * DECAYS[decay] * jax.random.uniform(ks[3], (bsz, l, h, dk)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (bsz, l, h))))


def _cast(args, dtype):
    q, k, v, g, beta = args
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _as_float32(args):
    """The operands as the kernel sees them, in float32: what rounding the
    inputs alone costs is not the kernel's."""
    return _cast(_cast(args, jnp.bfloat16), jnp.float32)


def _close(got, want, exact: bool, name=""):
    """float32: to rounding.  bfloat16 operands: within the rounding of
    the products' operands."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert np.isfinite(got).all(), name
    scale = float(np.max(np.abs(want)))
    if exact:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5 * scale,
                                   err_msg=name)
    else:
        assert float(np.max(np.abs(got - want))) < 0.05 * scale, name


def _counters_but_builds(delta):
    """A registry window's counters but the programs JAX built in it
    (``jit_builds_total``)."""
    return {k: v for k, v in delta["counters"].items()
            if not k.startswith("jit_builds_total")}


CASES = [pytest.param(decay, dtype, id=f"{decay}-{jnp.dtype(dtype).name}")
         for decay in DECAYS for dtype in (jnp.float32, jnp.bfloat16)]


def test_the_decays_overflow_where_factored():
    """What the exact pairs inside a sub-chunk are for: a chunk's summed
    log-decay is past float32's exponent at both decays."""
    for decay in DECAYS:
        g = _inputs(64, decay, seed=3, bsz=1, h=1)[3]
        assert float(jnp.min(jnp.sum(g, axis=1))) < -math.log(
            np.finfo(np.float32).max)


def _kernel(*args):
    return scan.kda_scan(*args, 64, 16, True)


def _chunked(*args):
    return kda_chunked(*args, 64, 16)


# jitted once, so that the cases of one shape and dtype share a compile
FORWARD = {f: jax.jit(f) for f in (_kernel, _chunked, kda_recurrent)}
COTANGENTS = {f: jax.jit(jax.grad(lambda w, *a, f=f: jnp.sum(w * f(*a)),
                                  argnums=range(1, 6)))
              for f in (_kernel, _chunked, kda_recurrent)}


@pytest.mark.usefixtures("exact_products")
@pytest.mark.parametrize("decay,dtype", CASES)
def test_forward_is_the_chunked_scan_and_the_recurrence(decay, dtype):
    args = _inputs(192, decay, seed=3)
    exact = dtype == jnp.float32
    assert scan._head_block(4) < 4            # more than one head block
    got = FORWARD[_kernel](*_cast(args, dtype))
    assert got.shape == args[2].shape and got.dtype == jnp.float32
    _close(got, FORWARD[_chunked](*_cast(args, dtype)), exact)
    _close(got, FORWARD[kda_recurrent](
        *(args if exact else _as_float32(args))), exact)


@pytest.mark.usefixtures("exact_products")
@pytest.mark.parametrize("decay,dtype", CASES)
def test_five_cotangents_are_the_chunked_scans_and_the_recurrences(
        decay, dtype):
    args = _inputs(128, decay, seed=1)
    exact = dtype == jnp.float32
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = COTANGENTS[_kernel](w, *_cast(args, dtype))
    chunked = COTANGENTS[_chunked](w, *_cast(args, dtype))
    by_steps = COTANGENTS[kda_recurrent](
        w, *(args if exact else _as_float32(args)))
    for name, g, r, s, arg in zip(NAMES, got, chunked, by_steps,
                                  _cast(args, dtype)):
        assert g.shape == arg.shape and g.dtype == arg.dtype, name
        _close(g, r, exact, name)
        _close(g, s, exact, name)


# ------------------------------------------------- under a checkpoint ---

# two chunks of 8, one head: the chunk-start states hold a state
REMAT_ARGS = _inputs(16, "init", seed=5, bsz=1, h=1)
REMAT_W = jax.random.normal(jax.random.PRNGKey(9), REMAT_ARGS[2].shape)


def _remat_block(*args):
    """A layer's use of the scan: its output scaled, cast and named as
    ``KDAMixer.forward`` names it, then a function whose backward pass
    needs that output."""
    o = checkpoint_name((scan.kda_scan(*args, 8, 8, True) * 0.5).astype(
        jnp.bfloat16), scan.RESIDUAL)
    return jnp.sum(REMAT_W * jnp.tanh(o.astype(jnp.float32)))


def _remat_grad(*kept):
    """The five cotangents of ``_remat_block`` under a checkpoint that
    keeps the names ``kept``."""
    return jax.grad(jax.checkpoint(
        _remat_block,
        policy=jax.checkpoint_policies.save_only_these_names(*kept)),
        argnums=range(5))


def _kernel_calls(jaxpr):
    """The Pallas kernels a jaxpr calls, by name, the nested jaxprs'
    included."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            calls.extend(_kernel_calls(sub))
    return calls


@pytest.mark.parametrize("kept,forwards", [((), 2), ((scan.RESIDUAL,), 1)],
                         ids=["neither", "both"])
def test_the_kept_residuals_take_the_forward_kernel_out_of_the_backward(
        kept, forwards):
    """Kept, the chunk-start states and the scaled output spare the
    backward pass its run of ``kda_scan_fwd``."""
    calls = _kernel_calls(jax.make_jaxpr(_remat_grad(*kept))(
        *REMAT_ARGS).jaxpr)
    assert sorted(calls) == ["kda_scan_bwd"] + ["kda_scan_fwd"] * forwards


def test_the_kept_residuals_give_the_same_gradients():
    """The kept values are those the recomputation writes: the cotangents
    with both names kept are to the bit those with neither (one program
    for the two, one compile)."""
    neither, both = jax.jit(lambda *a: (_remat_grad()(*a), _remat_grad(
        scan.RESIDUAL)(*a)))(*REMAT_ARGS)
    for name, a, b in zip(NAMES, neither, both):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_array_equal(a, b, err_msg=name)


def _specs(bsz, l, h, dk, dv, chunk, sub, dtype):
    from tests.mosaic_aot import spec

    return (spec((bsz, l, h, dk), dtype), spec((bsz, l, h, dk), dtype),
            spec((bsz, l, h, dv), dtype), spec((bsz, l, h, dk), jnp.float32),
            spec((bsz, l, h), jnp.float32))


def _compile_both(bsz, l, h, dk, dv, chunk, sub, dtype):
    """The forward with its residual and the backward, through the TPU's
    compiler; the kernels' names in the compiled programs."""
    from tests.mosaic_aot import spec, tpu_compile

    args = _specs(bsz, l, h, dk, dv, chunk, sub, dtype)
    fwd = tpu_compile(lambda *a: scan._scan_fwd(*a, chunk, sub, False,
                                                with_states=True), *args)
    bwd = tpu_compile(
        lambda *a: scan._scan_bwd(*a, chunk, sub, False), *args,
        spec((bsz, l // chunk, h * dv, dk), jnp.float32),
        spec((bsz, l, h, dv), jnp.float32))
    assert "kda_scan_fwd" in fwd.as_text()
    assert "kda_scan_bwd" in bwd.as_text()


def _ok(bsz, l, h, dk, dv, chunk, sub, dtype):
    return scan.shapes_ok((bsz, l, h, dk), dv, chunk, sub, dtype)


class TestShapesOkIsTheCompilersAnswer:
    """Both sides of ``shapes_ok``: what it admits Mosaic compiles, forward
    and backward; what Mosaic refuses it refuses."""

    @pytest.mark.parametrize("shape", [
        CELL,
        dict(CELL, l=256, dtype=jnp.float32),
        dict(CELL, l=256, h=3),                     # a head a grid step
        dict(CELL, l=256, h=2, dv=256),             # values of two tiles
        dict(CELL, l=64, h=2, chunk=32, sub=8),
    ], ids=["cell", "float32", "three-heads", "values-of-256",
            "chunk-32-sub-8"])
    def test_admitted_and_compiled(self, shape):
        assert _ok(**shape)
        _compile_both(**shape)

    @pytest.mark.parametrize("shape,why", [
        (dict(CELL, l=24, h=2, chunk=12, sub=4), "divisible by 8 and 128"),
        (dict(CELL, l=128, h=2, dtype=jnp.float16),
         "Mosaic failed to compile"),
        (dict(CELL, l=128, h=2, dtype=jnp.int8), "Mosaic failed to compile"),
    ], ids=["chunk-12", "float16", "int8"])
    def test_refused_by_both(self, shape, why):
        assert not _ok(**shape)
        with pytest.raises(Exception, match=why):
            _compile_both(**shape)

    def test_a_ragged_length_or_narrow_head_is_refused_before_any_compile(
            self):
        assert not _ok(**dict(CELL, l=8100))
        assert not _ok(**dict(CELL, chunk=64, sub=24))
        assert not _ok(**dict(CELL, dk=64))
        assert not _ok(**dict(CELL, dv=96))


class TestTheMixersDispatch:
    def _series(self, monkeypatch, **mixer):
        from tests.mosaic_aot import selected_series

        kw = dict(n_heads=32, head_dim=128, chunk_size=64)
        kw.update(mixer)
        m = KDAMixer(2304, name="kda_dispatch", **kw)
        heads = (2, 8192, m.n_heads, m.head_dim)
        f32 = jnp.float32
        return selected_series(
            monkeypatch, m._scan,
            *(jax.ShapeDtypeStruct(heads, jnp.bfloat16) for _ in "qkv"),
            jax.ShapeDtypeStruct(heads, f32),
            jax.ShapeDtypeStruct(heads[:3], f32))

    def test_pallas_at_the_cells_shape(self, monkeypatch):
        from tests.mosaic_aot import series

        assert self._series(monkeypatch) == series("kda_scan", "pallas")

    def test_reference_at_a_refused_shape(self, monkeypatch):
        from tests.mosaic_aot import series

        assert self._series(monkeypatch, head_dim=64) == series(
            "kda_scan", "reference")

    def test_reference_off_the_tpu(self):
        from analytics_zoo_tpu.observe.metrics import METRICS

        assert not dispatch.on_tpu()
        m = KDAMixer(24, 2, 128, chunk_size=32, name="kda_off_tpu")
        u = jnp.zeros((1, 32, 24))
        p = m.build_params(jax.random.PRNGKey(1), u.shape)
        mark = METRICS.snapshot()
        jax.eval_shape(m.forward, p, u)
        assert _counters_but_builds(METRICS.delta(mark)) == {
            'ops_kernel_selected_total{kernel="kda_scan",path="reference"}':
                1}


@pytest.mark.usefixtures("exact_products")
def test_the_mixers_padded_length_through_the_kernels(monkeypatch):
    """A length off the chunk, on the ``pallas`` path (the kernels under
    the interpreter): the padding changes no state, the outputs and the
    five cotangents are the recurrence's over the length asked for, and
    the path is counted."""
    from analytics_zoo_tpu.observe.metrics import METRICS
    from tests.mosaic_aot import series

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    kernel = scan.kda_scan
    monkeypatch.setattr(scan, "kda_scan", lambda *a: kernel(*a, True))
    m = KDAMixer(24, 4, 128, chunk_size=64, name="kda_padded")
    args = _inputs(100, "init", seed=4)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def both(f):
        return jax.value_and_grad(
            lambda *a: (lambda o: (jnp.sum(w * o), o))(f(*a)),
            argnums=range(5), has_aux=True)(*args)

    mark = METRICS.snapshot()
    with pytest.warns(UserWarning, match="padded by 28"):
        (_, got), dgot = both(m._scan)
    assert _counters_but_builds(METRICS.delta(mark)) == {
        series("kda_scan", "pallas"): 1}
    (_, want), dwant = both(kda_recurrent)
    assert got.shape == want.shape
    _close(got, want, True)
    for name, u, v in zip(NAMES, dgot, dwant):
        _close(u, v, True, name)
