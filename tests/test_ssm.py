"""The Mamba-2 mixer's chunked scan against the recurrence a step at a time,
float32: outputs and every parameter's gradient."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.nn.layers.ssm import Mamba2Mixer, ssd_chunked
from analytics_zoo_tpu.observe.metrics import METRICS


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _recurrence(x, dt, a, b, c):
    """y_t = S_t C_t with S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T, a
    step at a time.  x (B, L, H, P), dt (B, L, H), a (H,), b, c
    (B, L, G, N)."""
    h, g = x.shape[2], b.shape[2]
    b, c = (jnp.repeat(t, h // g, axis=2) for t in (b, c))

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs                    # (B, H, ...)
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    s0 = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:])
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


def _inputs(l, decay, groups=1, seed=0):
    """Strong decay: dt * A down to about -40 a step, so a chunk's far
    corner underflows and, unmasked, the near one would overflow."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    bsz, h, p, n = 2, 4, 8, 6
    x = jax.random.normal(ks[0], (bsz, l, h, p))
    b = jax.random.normal(ks[1], (bsz, l, groups, n))
    c = jax.random.normal(ks[2], (bsz, l, groups, n))
    hi = {"weak": 0.05, "strong": 2.5}[decay]
    dt = jax.random.uniform(ks[3], (bsz, l, h), minval=0.001, maxval=hi)
    a = -jax.random.uniform(ks[4], (h,), minval=1.0, maxval=16.0)
    return x, dt, a, b, c


# (length, chunk): one chunk, two, sixteen, a chunk of one step
PAIRS = [(16, 16), (32, 16), (64, 4), (8, 1)]


@pytest.mark.parametrize("decay", ["weak", "strong"])
@pytest.mark.parametrize("l,chunk", PAIRS)
def test_chunked_scan_is_the_recurrence(l, chunk, decay):
    args = _inputs(l, decay)
    want = jax.jit(_recurrence)(*args)
    got = jax.jit(ssd_chunked, static_argnums=5)(*args, chunk)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("decay", ["weak", "strong"])
@pytest.mark.parametrize("l,chunk", PAIRS)
def test_chunked_scan_has_the_recurrences_gradients(l, chunk, decay):
    args = _inputs(l, decay, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(w * _recurrence(*a)),
                            argnums=range(5)))(*args)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(w * ssd_chunked(*a, chunk)),
                           argnums=range(5)))(*args)
    for name, g, r in zip(("x", "dt", "a", "b", "c"), got, want):
        assert np.isfinite(np.asarray(g)).all(), name
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=name)


def test_two_groups_share_b_and_c_among_their_heads():
    args = _inputs(32, "weak", groups=2, seed=2)
    np.testing.assert_allclose(ssd_chunked(*args, 8), _recurrence(*args),
                               rtol=2e-5, atol=2e-5)


def _mixer_by_steps(mixer, p, u):
    """The mixer's docstring, with the scan a step at a time."""
    d_in, gn = mixer.d_inner, mixer.n_groups * mixer.d_state
    z, xbc, dt = jnp.split(u @ p["in_proj"]["kernel"],
                           [d_in, d_in + mixer.conv_dim], axis=-1)
    l = u.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (mixer.d_conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv"]["kernel"][j] * padded[:, j:j + l]
                          for j in range(mixer.d_conv)) + p["conv"]["bias"])
    x, b, c = jnp.split(xbc, [d_in, d_in + gn], axis=-1)
    x = x.reshape(x.shape[:2] + (mixer.n_heads, mixer.head_dim))
    b, c = (t.reshape(t.shape[:2] + (mixer.n_groups, mixer.d_state))
            for t in (b, c))
    y = _recurrence(x, jax.nn.softplus(dt + p["dt_bias"]),
                    -jnp.exp(p["A_log"]), b, c)
    y = (y + p["D"][:, None] * x).reshape(u.shape[:2] + (d_in,))
    y = y * jax.nn.silu(z)
    y = y.reshape(y.shape[:2] + (mixer.n_groups, -1))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + mixer.epsilon)
    return (y.reshape(u.shape[:2] + (d_in,)) * p["norm"]["gamma"]
            ) @ p["out_proj"]["kernel"]


@pytest.mark.parametrize("l,chunk,groups", [(16, 16, 1), (32, 8, 1),
                                            (64, 4, 2)])
def test_mixer_meets_the_recurrence_in_output_and_every_gradient(
        l, chunk, groups):
    mixer = Mamba2Mixer(32, n_heads=4, head_dim=16, d_state=8,
                        n_groups=groups, chunk_size=chunk,
                        name=f"mixer_{l}_{chunk}_{groups}")
    u = jax.random.normal(jax.random.PRNGKey(0), (2, l, 32))
    p = mixer.build_params(jax.random.PRNGKey(1), u.shape)
    assert set(p) == {"in_proj", "conv", "dt_bias", "A_log", "D", "norm",
                      "out_proj"}
    assert p["in_proj"]["kernel"].shape == (32, 2 * 64 + 2 * groups * 8 + 4)
    assert p["conv"]["kernel"].shape == (4, 64 + 2 * groups * 8)
    # the Mamba-2 defaults: A in 1..16, delta in 1e-3..1e-1
    assert ((p["A_log"] >= 0) & (p["A_log"] <= np.log(16.0))).all()
    delta = jax.nn.softplus(p["dt_bias"])
    assert ((delta > 9e-4) & (delta < 0.11)).all()
    # weights that make every term count
    p = jax.tree_util.tree_map(
        lambda a, k: a + 0.3 * jax.random.normal(k, a.shape), p,
        jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(p),
            list(jax.random.split(jax.random.PRNGKey(2), 8))))
    w = jax.random.normal(jax.random.PRNGKey(3), u.shape)
    ours = jax.jit(jax.value_and_grad(lambda q: (
        lambda y: (jnp.sum(w * y), y))(mixer.forward(q, u)), has_aux=True))
    theirs = jax.jit(jax.value_and_grad(lambda q: (
        lambda y: (jnp.sum(w * y), y))(_mixer_by_steps(mixer, q, u)),
        has_aux=True))
    (_, y), got = ours(p)
    (_, y_want), want = theirs(p)
    np.testing.assert_allclose(y, y_want, rtol=1e-4, atol=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(
            g, r, rtol=2e-4, atol=2e-5 * scale,
            err_msg=jax.tree_util.keystr(path))


def test_a_length_that_is_no_multiple_of_the_chunk_is_padded_and_said():
    mixer = Mamba2Mixer(32, n_heads=4, head_dim=16, d_state=8, chunk_size=8,
                        name="mixer_padded")
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 32))
    p = mixer.build_params(jax.random.PRNGKey(1), u.shape)
    by_steps = jax.jit(lambda v: _mixer_by_steps(mixer, p, v))
    with pytest.warns(UserWarning, match="padded by 4 steps"):
        got = jax.jit(mixer.forward)(p, u)
    np.testing.assert_allclose(got, by_steps(u), rtol=1e-4, atol=1e-5)
    # a sequence shorter than the chunk is one chunk, unpadded
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        short = jax.jit(mixer.forward)(p, u[:, :5])
    np.testing.assert_allclose(short, by_steps(u[:, :5]), rtol=1e-4,
                               atol=1e-5)


def test_bfloat16_keeps_decays_and_states_in_float32():
    """Operands of the compute dtype, float32 out of the scan: against the
    float32 recurrence within bfloat16's rounding of the operands."""
    args = _inputs(64, "weak", seed=4)
    x, dt, a, b, c = args
    got = ssd_chunked(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
                      c.astype(jnp.bfloat16), 16)
    assert got.dtype == jnp.float32
    want = _recurrence(*args)
    assert float(jnp.max(jnp.abs(got - want))) < 0.05 * float(
        jnp.max(jnp.abs(want)))


def test_scopes_and_the_selection_counter():
    mixer = Mamba2Mixer(32, n_heads=4, head_dim=16, d_state=8, chunk_size=8,
                        name="mixer_scoped")
    u = jnp.zeros((1, 16, 32))
    p = mixer.build_params(jax.random.PRNGKey(1), u.shape)
    before = METRICS.snapshot()
    text = jax.jit(mixer.forward).lower(p, u).as_text(debug_info=True)
    assert "zoo:ssm/mixer" in text and "zoo:ssm/mixer/zoo:ssm/scan" in text
    counters = METRICS.delta(before)["counters"]
    assert counters[
        'ops_kernel_selected_total{kernel="ssm_scan",path="reference"}'] == 1
    with pytest.raises(ValueError, match="groups"):
        Mamba2Mixer(32, n_heads=4, head_dim=16, d_state=8, n_groups=3)
