"""Sequence-parallel ring attention (ops/ring_attention.py, ISSUE 17).

Parity: the sharded ring — K/V blocks rotating over the mesh's ``seq``
axis via ppermute, folded hop-by-hop into the online-softmax carry —
must be numerically indistinguishable from single-device attention over
the *gathered* sequence, forward AND backward, on both the pure-JAX
hops and the flash-kernel hops (``force="interpret"``, the CPU tier's
stand-in for the Mosaic path).  Routing: the counted dispatch contract
(mesh / min-length / knob / force) decides ring-vs-local, and the
decision is visible both in ``ops_kernel_selected_total`` and in the
jaxpr (a ``ppermute`` only appears when the ring is actually taken).
Memory: inside the shard_map body no array may exceed the per-shard
logits block — the O(L/ways) per-chip residency the ring exists for
(docs/PARALLELISM.md "Sequence parallelism" states it; this is the
live check).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from analytics_zoo_tpu.ops import dispatch
from analytics_zoo_tpu.ops.attention import blockwise_attention
from analytics_zoo_tpu.ops.ring_attention import (RING_MIN_LEN,
                                                  ring_attention)


def _mesh(ways, axis="seq"):
    devs = jax.devices()
    if len(devs) < ways:
        pytest.skip(f"needs {ways} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:ways]), (axis,))


def _qkv(b=1, h=2, l=256, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda k: jax.random.normal(k, (b, h, l, d),
                                     jnp.float32).astype(dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


def _oracle(path):
    """Single-device reference for a given hop backend: the pure-JAX
    hops fold the same math as blockwise_attention; the interpret hops
    run the flash kernel, so parity is judged against the *single-chip
    flash* run under the same interpreter."""
    if path == dispatch.PATH_INTERPRET:
        from analytics_zoo_tpu.ops.flash_attention import flash_attention

        return lambda q, k, v, causal: flash_attention(
            q, k, v, causal, None, 32, 32, True)
    return lambda q, k, v, causal: blockwise_attention(
        q, k, v, causal=causal, block_size=32)


class TestRingParity:
    """fwd + bwd vs single-device attention, 2- and 4-way shards."""

    @pytest.mark.parametrize("ways", [2, 4])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("path", [dispatch.PATH_REFERENCE,
                                      dispatch.PATH_INTERPRET])
    def test_forward_matches_single_device(self, ways, causal, path):
        mesh = _mesh(ways)
        q, k, v = _qkv(l=128, d=32, seed=ways)
        out = ring_attention(q, k, v, mesh=mesh, causal=causal,
                             block_q=32, block_k=32, force=path)
        ref = _oracle(path)(q, k, v, causal)
        assert out.shape == q.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("ways", [2, 4])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("path", [dispatch.PATH_REFERENCE,
                                      dispatch.PATH_INTERPRET])
    def test_grads_match_single_device(self, ways, causal, path):
        mesh = _mesh(ways)
        q, k, v = _qkv(l=64, d=16, seed=7 * ways)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(
                q, k, v, mesh=mesh, causal=causal, block_q=32,
                block_k=32, force=path) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(_oracle(path)(q, k, v, causal) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_ring, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
                err_msg=f"d{name} diverged ({ways}-way, causal={causal},"
                        f" {path})")

    def test_ragged_length_causal(self):
        # L % ways != 0: tail-padded; causal masking hides the pad keys
        mesh = _mesh(4)
        q, k, v = _qkv(l=90, d=16, seed=3)
        out = ring_attention(q, k, v, mesh=mesh, causal=True,
                             force=dispatch.PATH_REFERENCE)
        ref = blockwise_attention(q, k, v, causal=True, block_size=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_ragged_length_full(self):
        # non-causal ragged routes to the pure-JAX hops (global key
        # positions >= L masked explicitly); knob "on" rings regardless
        # of the RING_MIN_LEN floor
        mesh = _mesh(4)
        q, k, v = _qkv(l=90, d=16, seed=4)
        out = ring_attention(q, k, v, mesh=mesh, causal=False, knob="on")
        ref = blockwise_attention(q, k, v, causal=False, block_size=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_inputs_keep_f32_carry(self):
        # the (m, l, acc) carry is f32 across hops: bf16 in/out must sit
        # at bf16 resolution from the f32 oracle, not compound per hop
        mesh = _mesh(4)
        q, k, v = _qkv(l=128, d=32, dtype=jnp.bfloat16, seed=5)
        out = ring_attention(q, k, v, mesh=mesh, causal=True, knob="on")
        assert out.dtype == jnp.bfloat16
        ref = blockwise_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True, block_size=32)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
        assert err < 3e-2, f"bf16 ring drifted {err} from f32 oracle"


class TestRingDispatch:
    """The counted routing contract: mesh / min-length / knob / force."""

    def _counter(self, path):
        from analytics_zoo_tpu.observe.metrics import METRICS

        key = ("ops_kernel_selected_total",
               (("kernel", "ring_attention"), ("path", path)))
        return METRICS.snapshot().counters.get(key, 0)

    def test_no_mesh_is_single_device_fallback(self):
        from analytics_zoo_tpu.observe.metrics import METRICS

        q, k, v = _qkv(l=64, d=16)
        before = self._counter(dispatch.PATH_REFERENCE)
        out = ring_attention(q, k, v, mesh=None)
        ref = blockwise_attention(q, k, v, causal=False,
                                  sm_scale=1.0 / 4.0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        assert self._counter(dispatch.PATH_REFERENCE) == before + 1

    def test_selection_metric_counts_ring_path(self):
        mesh = _mesh(2)
        q, k, v = _qkv(l=64, d=16)
        before = self._counter(dispatch.PATH_REFERENCE)
        ring_attention(q, k, v, mesh=mesh, force=dispatch.PATH_REFERENCE)
        assert self._counter(dispatch.PATH_REFERENCE) == before + 1

    def _has_ppermute(self, **kw):
        mesh = kw.pop("mesh", _mesh(4))
        shape = jax.ShapeDtypeStruct((1, 2, kw.pop("l"), 16), jnp.float32)
        jxp = jax.make_jaxpr(lambda a, b, c: ring_attention(
            a, b, c, mesh=mesh, **kw))(shape, shape, shape)
        return "ppermute" in str(jxp)

    def test_auto_rings_only_above_min_len(self):
        # the jaxpr is the ground truth for ring-vs-local: a ppermute
        # only exists when the K/V exchange was actually scheduled
        assert not self._has_ppermute(l=256)            # < RING_MIN_LEN
        assert self._has_ppermute(l=RING_MIN_LEN)       # auto engages
        assert self._has_ppermute(l=256, knob="on")     # knob overrides
        assert not self._has_ppermute(l=RING_MIN_LEN, knob="off")
        assert not self._has_ppermute(l=RING_MIN_LEN, mesh=None)

    @pytest.mark.parametrize("l,path", [
        (4096, dispatch.PATH_PALLAS),       # 1024-token shards
        (4128, dispatch.PATH_REFERENCE),    # 1032-token shards: block_q=8
    ])
    def test_tpu_auto_takes_kernel_hops_only_on_lane_multiples(
            self, monkeypatch, l, path):
        from tests.mosaic_aot import selected_series, series

        mesh = _mesh(4)
        shape = jax.ShapeDtypeStruct((1, 2, l, 128), jnp.bfloat16)
        got = selected_series(
            monkeypatch, lambda a, b, c: ring_attention(
                a, b, c, mesh=mesh, causal=True), shape, shape, shape)
        assert got == series("ring_attention", path)

    def test_mosaic_verdict_on_each_side_of_the_shard_rule(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from tests.mosaic_aot import spec, tpu_compile, tpu_devices

        mesh = Mesh(np.asarray(tpu_devices()), ("seq",))
        seq = NamedSharding(mesh, P(None, None, "seq", None))
        hops = lambda a, b, c: ring_attention(
            a, b, c, mesh=mesh, causal=True, force=dispatch.PATH_PALLAS)
        ok = spec((1, 2, 4096, 128), jnp.bfloat16, seq)
        tpu_compile(hops, ok, ok, ok)
        bad = spec((1, 2, 4128, 128), jnp.bfloat16, seq)
        with pytest.raises(ValueError, match="divisible by 8 and 128"):
            tpu_compile(hops, bad, bad, bad)

    def test_more_seq_shards_than_devices_is_an_error(self):
        # never a quiet fall back to single-device attention
        from analytics_zoo_tpu.parallel.sharding import seq_mesh

        assert seq_mesh(4).shape["seq"] == 4
        with pytest.raises(ValueError, match="seq_shards=16 needs"):
            seq_mesh(16)

    def test_force_kernel_without_mesh_rejected(self):
        q, k, v = _qkv(l=64, d=16)
        with pytest.raises(ValueError, match="needs a mesh"):
            ring_attention(q, k, v, mesh=None,
                           force=dispatch.PATH_INTERPRET)

    def test_force_kernel_ragged_noncausal_rejected(self):
        mesh = _mesh(4)
        q, k, v = _qkv(l=90, d=16)
        with pytest.raises(ValueError, match="needs a mesh"):
            ring_attention(q, k, v, mesh=mesh, causal=False,
                           force=dispatch.PATH_INTERPRET)

    def test_kv_shape_mismatch_rejected(self):
        q, k, v = _qkv(l=64, d=16)
        with pytest.raises(ValueError, match="k/v shapes differ"):
            ring_attention(q, k[:, :1], v, mesh=None)

    def test_cross_attention_rejected(self):
        q, _, _ = _qkv(l=64, d=16)
        k, v, _ = _qkv(l=32, d=16)
        with pytest.raises(ValueError, match="self-attention only"):
            ring_attention(q, k, v, mesh=None)

    def test_seq_shards_config_knob_reaches_dispatch(self):
        from analytics_zoo_tpu import init_zoo_context

        try:
            init_zoo_context(ring_attention="off")
            assert dispatch.config_knob("ring_attention", "auto") == "off"
        finally:
            init_zoo_context()


class TestRingMemory:
    """Per-chip peak attention memory is O(L/ways): inside the
    shard_map body no array may exceed the per-shard logits block —
    ways² smaller than the O(L²) matrix single-device attention
    would need, and the whole point of streaming K/V over ICI."""

    @staticmethod
    def _inner_avals(jaxpr, acc):
        for eqn in jaxpr.eqns:
            sub = eqn.params.get("jaxpr")
            if sub is not None:
                TestRingMemory._inner_avals(
                    getattr(sub, "jaxpr", sub), acc)
            for br in eqn.params.get("branches", ()):
                TestRingMemory._inner_avals(
                    getattr(br, "jaxpr", br), acc)
            for v in eqn.outvars:
                a = getattr(v, "aval", None)
                if a is not None and getattr(a, "shape", None) is not None:
                    acc.append(a)

    def test_no_array_beyond_per_shard_logits(self):
        b, h, l, d, ways = 1, 2, 4096, 16, 4
        mesh = _mesh(ways)
        shape = jax.ShapeDtypeStruct((b, h, l, d), jnp.float32)
        jxp = jax.make_jaxpr(lambda a, bb, c: ring_attention(
            a, bb, c, mesh=mesh, causal=True, knob="on"))(
                shape, shape, shape)
        inner = []
        for eqn in jxp.jaxpr.eqns:
            if "shard_map" in eqn.primitive.name:
                body = eqn.params.get("jaxpr")
                self._inner_avals(getattr(body, "jaxpr", body), inner)
        assert inner, "ring jaxpr lost its shard_map body"
        per_shard_logits = b * h * (l // ways) ** 2
        biggest = max(int(np.prod(a.shape)) for a in inner if a.shape)
        assert biggest <= per_shard_logits, (
            f"per-chip intermediate of {biggest} elements exceeds the "
            f"(L/ways)² logits block ({per_shard_logits})")
        # and nothing per-chip ever sees the full sequence axis
        assert all(l not in a.shape for a in inner)
