"""Fused dequantize-matmul kernel: packing, parity, serving wiring.

``ops/dequant_matmul.py`` stores serving weights quantized (int8 at 1/4,
nibble-packed int4 at 1/8 the f32 HBM footprint) and decodes tiles
in-registers after the VMEM load — the f32 weight never materialises in
HBM.  Here the kernel runs in interpreter mode on CPU (the same program
the TPU executes) and must match the pure-JAX dequantize-then-matmul
oracle bit-for-bit-close, across odd/ragged shapes, through the custom
VJP, and end-to-end through the serving replica path behind the
``serving_weight_dtype`` knob.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.dequant_matmul import (
    dequant_matmul,
    dequant_matmul_reference,
    pack_int4,
    quantize_weights,
    unpack_int4,
)


def _qcase(k, n, bits, seed=0):
    rs = np.random.RandomState(seed)
    w = rs.randn(k, n).astype(np.float32) * 0.1
    q, scale = quantize_weights(w, bits=bits)
    return w, q, scale


class TestPacking:
    @pytest.mark.parametrize("k", [2, 6, 64])
    def test_roundtrip_even_rows(self, k):
        rs = np.random.RandomState(k)
        q4 = jnp.asarray(rs.randint(-8, 8, size=(k, 5)).astype(np.int8))
        np.testing.assert_array_equal(
            np.asarray(unpack_int4(pack_int4(q4), k)), np.asarray(q4))

    def test_roundtrip_odd_rows(self):
        # odd K: the last byte carries a zero nibble, rows= disambiguates
        rs = np.random.RandomState(1)
        q4 = jnp.asarray(rs.randint(-8, 8, size=(33, 7)).astype(np.int8))
        packed = pack_int4(q4)
        assert packed.shape == (17, 7)
        np.testing.assert_array_equal(
            np.asarray(unpack_int4(packed, 33)), np.asarray(q4))

    def test_quantize_weights_footprint_and_error(self):
        w, q8, s8 = _qcase(128, 32, 8)
        _, q4, s4 = _qcase(128, 32, 4)
        assert q8.dtype == jnp.int8 and q8.nbytes == w.size
        assert q4.nbytes * 8 == w.nbytes          # exactly 1/8 of f32
        # per-channel symmetric: int8 reconstruction inside ~1%, int4
        # (16 levels) inside ~15%
        w8 = np.asarray(q8.astype(np.float32) * s8)
        assert np.linalg.norm(w8 - w) / np.linalg.norm(w) < 0.02
        w4 = np.asarray(unpack_int4(q4, 128).astype(np.float32) * s4)
        assert np.linalg.norm(w4 - w) / np.linalg.norm(w) < 0.15

    def test_bad_bits_rejected(self):
        with pytest.raises(ValueError, match="bits"):
            quantize_weights(np.ones((4, 4), np.float32), bits=2)


class TestKernelParity:
    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("m,k,n", [(4, 16, 8), (7, 33, 12),
                                       (16, 130, 256)])
    def test_forward_matches_reference(self, bits, m, k, n):
        # ragged everything: odd K (int4 pad nibble), non-multiple-of-
        # block M/N, wide-enough N to cross a lane tile
        w, q, s = _qcase(k, n, bits, seed=m + k)
        x = jnp.asarray(np.random.RandomState(9).randn(m, k)
                        .astype(np.float32))
        got = dequant_matmul(x, q, s, bits=bits, rows=k, interpret=True)
        want = dequant_matmul_reference(x, q, s, bits=bits, rows=k)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_leading_batch_dims(self):
        w, q, s = _qcase(24, 10, 8)
        x = jnp.asarray(np.random.RandomState(3).randn(2, 5, 24)
                        .astype(np.float32))
        got = dequant_matmul(x, q, s, interpret=True)
        assert got.shape == (2, 5, 10)
        np.testing.assert_allclose(
            got, dequant_matmul_reference(x, q, s), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_grad_matches_reference(self, bits):
        w, q, s = _qcase(32, 12, bits, seed=5)
        x = jnp.asarray(np.random.RandomState(4).randn(6, 32)
                        .astype(np.float32))

        def loss(fn):
            return lambda a: jnp.sum(fn(a) ** 2)

        g_k = jax.grad(loss(lambda a: dequant_matmul(
            a, q, s, bits=bits, rows=32, interpret=True)))(x)
        g_r = jax.grad(loss(lambda a: dequant_matmul_reference(
            a, q, s, bits=bits, rows=32)))(x)
        np.testing.assert_allclose(g_k, g_r, rtol=1e-5, atol=1e-5)

    def test_int8_dot_weight_only_routes_through_kernel(self):
        from analytics_zoo_tpu.ops.quantization import (int8_dot,
                                                        quantize_tensor)

        rs = np.random.RandomState(0)
        w = rs.randn(40, 20).astype(np.float32) * 0.1
        x = jnp.asarray(rs.randn(8, 40).astype(np.float32))
        wq, wscale = quantize_tensor(w)
        got = int8_dot(x, jnp.asarray(wq),
                       jnp.asarray(wscale).reshape(-1), weight_only=True)
        want = x @ (jnp.asarray(wq).astype(jnp.float32)
                    * jnp.asarray(wscale).reshape(1, -1))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _trained_net(in_dim=12, out_dim=6):
    from analytics_zoo_tpu.nn import Sequential, reset_name_scope
    from analytics_zoo_tpu.nn.layers.core import Activation, Dense
    from analytics_zoo_tpu.train.optimizers import Adam

    reset_name_scope()
    net = Sequential([Dense(32, input_shape=(in_dim,)), Activation("relu"),
                      Dense(out_dim)])
    net.compile(optimizer=Adam(1e-2), loss="mse")
    rs = np.random.RandomState(0)
    x = rs.randn(96, in_dim).astype(np.float32)
    net.fit(x, rs.randn(96, out_dim).astype(np.float32), batch_size=32,
            nb_epoch=1, verbose=False)
    return net, x


class TestMosaicShapeRules:
    """The K-blocking rule: auto-dispatch on TPU selects the kernel only
    where its K tiles are whole blocks Mosaic accepts (K within one
    512-row block, or a multiple of 128) — path and counted series with
    ``on_tpu`` patched to true, and the TPU compiler's verdict on each
    side (tests/mosaic_aot.py)."""

    @staticmethod
    def _args(k, bits, m=32, n=1024):
        rows = (k + 1) // 2 if bits == 4 else k
        return ((m, k), jnp.float32), ((rows, n), jnp.int8), \
            ((1, n), jnp.float32)

    @pytest.mark.parametrize("bits", [8, 4])
    @pytest.mark.parametrize("k,kernel", [
        (512, True), (511, True), (1024, True), (640, True),
        (513, False), (1000, False)])
    def test_path_and_series(self, monkeypatch, bits, k, kernel):
        from tests.mosaic_aot import selected_series, series

        got = selected_series(
            monkeypatch,
            lambda x, q, s: dequant_matmul(x, q, s, bits=bits, rows=k),
            *(jax.ShapeDtypeStruct(*a) for a in self._args(k, bits)))
        assert got == series("dequant_matmul",
                             "pallas" if kernel else "reference")

    @pytest.mark.parametrize("bits", [8, 4])
    def test_mosaic_compiles_both_sides_of_one_block(self, bits):
        from analytics_zoo_tpu.ops.dequant_matmul import _dq
        from tests.mosaic_aot import spec, tpu_compile

        for k in (511, 640):
            tpu_compile(lambda x, q, s: _dq(x, q, s, bits, k, False),
                        *(spec(*a) for a in self._args(k, bits)))

    def test_mosaic_refuses_an_untileable_k(self):
        from analytics_zoo_tpu.ops.dequant_matmul import _dq
        from tests.mosaic_aot import spec, tpu_compile

        with pytest.raises(ValueError, match="divisible by 8 and 128"):
            tpu_compile(lambda x, q, s: _dq(x, q, s, 8, 1000, False),
                        *(spec(*a) for a in self._args(1000, 8)))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_interpret_parity_past_one_block(self, bits):
        # the smallest K the rule admits beyond a single block: two
        # 320-row tiles, the int4 leg unpacking nibbles per tile
        k = 640
        w, q, s = _qcase(k, 128, bits, seed=7)
        x = jnp.asarray(np.random.RandomState(8).randn(8, k)
                        .astype(np.float32))
        got = dequant_matmul(x, q, s, bits=bits, rows=k, interpret=True)
        want = dequant_matmul_reference(x, q, s, bits=bits, rows=k)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


class TestServingWeightDtype:
    """The replica path: weights stored quantized end-to-end, Dense
    fusing the dequant into its matmul, top-1 stable vs float32."""

    def _models(self, weight_dtype):
        from analytics_zoo_tpu.deploy import InferenceModel

        net, x = _trained_net()
        f32 = InferenceModel.from_keras_net(
            net, net.estimator.params, net.estimator.state)
        q = InferenceModel.from_keras_net(
            net, net.estimator.params, net.estimator.state,
            weight_dtype=weight_dtype)
        return f32, q, x

    @pytest.mark.parametrize("weight_dtype,rel_bound",
                             [("int8", 1e-2), ("int4", 2e-1)])
    def test_quantized_forward_parity(self, weight_dtype, rel_bound):
        f32, q, x = self._models(weight_dtype)
        yf = np.asarray(f32.predict(x[:32]))
        yq = np.asarray(q.predict(x[:32]))
        rel = np.linalg.norm(yq - yf) / np.linalg.norm(yf)
        assert rel < rel_bound, rel
        top1 = (yq.argmax(-1) == yf.argmax(-1)).mean()
        floor = 1.0 if weight_dtype == "int8" else 0.9
        assert top1 >= floor, top1
        assert q._weight_dtype == weight_dtype

    def test_int4_param_tree_is_packed(self):
        """Dense kernels ride as nibble-packed q4 leaves — the stored
        tree really is ~1/8 the f32 bytes for the big matmul weights."""
        from analytics_zoo_tpu.deploy.inference import quantize_pytree

        net, _ = _trained_net()
        params = net.estimator.params
        qp = quantize_pytree(params, min_size=64, bits=4)
        q_leaves = [v for sub in qp.values() if isinstance(sub, dict)
                    for kk, v in sub.items()
                    if isinstance(v, dict) and "q4" in v]
        assert q_leaves, "no int4 leaves in the quantized tree"
        for leaf in q_leaves:
            rows = 2 * leaf["q4"].shape[0]
            assert leaf["q4"].nbytes * 8 == rows * leaf["q4"].shape[1] * 4

    def test_legacy_int8_flag_still_works(self):
        from analytics_zoo_tpu.deploy import InferenceModel

        net, x = _trained_net()
        m = InferenceModel.from_keras_net(
            net, net.estimator.params, net.estimator.state, int8=True)
        assert m._weight_dtype == "int8"
        out = np.asarray(m.predict(x[:8]))
        assert out.shape == (8, 6) and np.all(np.isfinite(out))

    def test_serving_weight_dtype_knob(self):
        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.deploy.inference import InferenceModel

        try:
            init_zoo_context(serving_weight_dtype="int8")
            net, x = _trained_net()
            m = InferenceModel.from_keras_net(
                net, net.estimator.params, net.estimator.state)
            assert m._weight_dtype == "int8"
        finally:
            init_zoo_context()

    def test_unknown_weight_dtype_rejected(self):
        from analytics_zoo_tpu.deploy import InferenceModel

        net, _ = _trained_net()
        with pytest.raises(ValueError, match="weight_dtype"):
            InferenceModel.from_keras_net(
                net, net.estimator.params, net.estimator.state,
                weight_dtype="int2")
