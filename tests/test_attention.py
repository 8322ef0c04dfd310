"""Attention tests: blockwise vs naive oracle, flash kernel (interpret
mode), MultiHeadAttention / TransformerLayer / BERT layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.nn.layers.attention import (
    BERT, MultiHeadAttention, TransformerBlock, TransformerLayer)
from analytics_zoo_tpu.ops.attention import (
    blockwise_attention, dot_product_attention, reference_attention)

KEY = jax.random.PRNGKey(0)


def _qkv(b=2, h=3, lq=16, lk=16, d=8, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, h, lq, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, h, lk, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, h, lk, d).astype(np.float32))
    return q, k, v


class TestBlockwise:
    def test_matches_reference(self):
        q, k, v = _qkv(lq=32, lk=48)
        ref = reference_attention(q, k, v)
        out = blockwise_attention(q, k, v, block_size=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_causal_matches_reference(self):
        q, k, v = _qkv(lq=24, lk=24)
        ref = reference_attention(q, k, v, causal=True)
        out = blockwise_attention(q, k, v, causal=True, block_size=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_causal_cross_length(self):
        """Lq < Lk (decode with cache): diagonal is offset."""
        q, k, v = _qkv(lq=4, lk=16)
        ref = reference_attention(q, k, v, causal=True)
        out = blockwise_attention(q, k, v, causal=True, block_size=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_mask_matches_reference(self):
        q, k, v = _qkv(lq=8, lk=24)
        rs = np.random.RandomState(1)
        mask = jnp.asarray(rs.rand(2, 1, 8, 24) > 0.3)
        ref = reference_attention(q, k, v, mask=mask)
        out = blockwise_attention(q, k, v, mask=mask, block_size=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16_inputs_accumulate_in_f32(self):
        """bf16 q/k/v: the scan carry is f32 so blockwise stays close to
        the f32 oracle, and the output dtype matches the inputs."""
        q, k, v = _qkv(lq=32, lk=64)
        ref = reference_attention(q, k, v)
        out = blockwise_attention(q.astype(jnp.bfloat16),
                                  k.astype(jnp.bfloat16),
                                  v.astype(jnp.bfloat16), block_size=16)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=2e-2, atol=2e-2)

    def test_fully_masked_rows_agree_across_paths(self):
        """A query row with no visible key returns zeros on every path."""
        q, k, v = _qkv(lq=4, lk=16)
        mask = jnp.ones((2, 1, 4, 16), bool).at[:, :, 2, :].set(False)
        ref = reference_attention(q, k, v, mask=mask)
        out = blockwise_attention(q, k, v, mask=mask, block_size=8)
        assert np.all(np.asarray(ref)[:, :, 2] == 0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_prob_dropout_unbiased(self):
        """Blockwise probability dropout: E[out] ~= undropped output, and
        rate=0 is exactly the undropped path."""
        q, k, v = _qkv(lq=8, lk=64)
        base = blockwise_attention(q, k, v, block_size=16)
        same = blockwise_attention(q, k, v, block_size=16,
                                   dropout_rate=0.0,
                                   dropout_rng=jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(base), np.asarray(same))
        outs = [blockwise_attention(q, k, v, block_size=16,
                                    dropout_rate=0.3,
                                    dropout_rng=jax.random.PRNGKey(s))
                for s in range(64)]
        mean = np.mean([np.asarray(o) for o in outs], axis=0)
        np.testing.assert_allclose(mean, np.asarray(base), atol=0.15)

    def test_ragged_kv_length(self):
        """Lk not divisible by block size (padding path)."""
        q, k, v = _qkv(lq=8, lk=21)
        ref = reference_attention(q, k, v)
        out = blockwise_attention(q, k, v, block_size=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_reference(self):
        q, k, v = _qkv(lq=16, lk=16, d=4)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        def loss_blk(q, k, v):
            return jnp.sum(
                blockwise_attention(q, k, v, causal=True, block_size=8) ** 2)

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_blk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestFlashKernel:
    """Pallas kernel in interpreter mode (the real-TPU path runs in
    ``chip_smoke.py`` and in the ``ouro-2.6b-fit-packed4k`` cell)."""

    def test_forward_matches_reference(self):
        from analytics_zoo_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(b=1, h=2, lq=256, lk=256, d=128)
        ref = reference_attention(q, k, v)
        out = flash_attention(q, k, v, False, None, 128, 128, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_forward_causal(self):
        from analytics_zoo_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(b=1, h=1, lq=256, lk=256, d=128)
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, True, None, 128, 128, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_backward_via_custom_vjp(self):
        from analytics_zoo_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(b=1, h=1, lq=128, lk=128, d=128)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, False, None, 128, 128,
                                           True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v) ** 2)

        g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_f, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-3)


class TestMultiHeadAttention:
    def test_self_attention_shape_and_grad(self):
        layer = MultiHeadAttention(nhead=4)
        x = jnp.asarray(np.random.randn(2, 10, 32).astype(np.float32))
        params, state = layer.init(KEY, x.shape)
        out, _ = layer.call(params, state, x)
        assert out.shape == (2, 10, 32)

        def loss(p):
            o, _ = layer.call(p, state, x)
            return jnp.sum(o ** 2)

        g = jax.grad(loss)(params)
        assert float(jnp.abs(g["q"]["kernel"]).sum()) > 0

    def test_cross_attention(self):
        layer = MultiHeadAttention(nhead=2)
        q = jnp.asarray(np.random.randn(2, 5, 16).astype(np.float32))
        kv = jnp.asarray(np.random.randn(2, 9, 16).astype(np.float32))
        params, state = layer.init(KEY, q.shape, kv.shape)
        out, _ = layer.call(params, state, q, kv)
        assert out.shape == (2, 5, 16)

    def test_cross_attention_different_kv_dim(self):
        """Memory features ≠ query features (regression: 2-input form
        must treat a 3D second input as kv, not as a mask)."""
        layer = MultiHeadAttention(nhead=2, hidden_size=16)
        q = jnp.asarray(np.random.randn(2, 5, 16).astype(np.float32))
        kv = jnp.asarray(np.random.randn(2, 9, 32).astype(np.float32))
        params, state = layer.init(KEY, q.shape, kv.shape)
        out, _ = layer.call(params, state, q, kv)
        assert out.shape == (2, 5, 16)

    def test_padding_mask_blocks_keys(self):
        layer = MultiHeadAttention(nhead=2)
        x = jnp.asarray(np.random.randn(1, 6, 16).astype(np.float32))
        params, state = layer.init(KEY, x.shape)
        mask = jnp.asarray([[1, 1, 1, 0, 0, 0]], jnp.float32)
        out_m, _ = layer.call(params, state, x, mask)
        # perturbing masked keys must not change the output
        x2 = x.at[:, 3:].set(x[:, 3:] + 100.0)
        out_m2, _ = layer.call(params, state, x2, mask)
        np.testing.assert_allclose(np.asarray(out_m[:, :3]),
                                   np.asarray(out_m2[:, :3]),
                                   rtol=1e-4, atol=1e-4)


class TestTransformerAndBert:
    def test_transformer_forward(self):
        layer = TransformerLayer(vocab=50, seq_len=12, n_block=2, nhead=2,
                                 hidden_size=32)
        ids = jnp.asarray(np.random.randint(0, 50, (2, 12)), jnp.int32)
        params, state = layer.init(KEY, ids.shape)
        out, _ = layer.call(params, state, ids)
        assert out.shape == (2, 12, 32)

    def test_transformer_causality(self):
        """Changing a later token must not affect earlier positions."""
        layer = TransformerLayer(vocab=50, seq_len=8, n_block=1, nhead=2,
                                 hidden_size=16, embedding_drop=0.0,
                                 hidden_drop=0.0, attn_drop=0.0)
        ids = jnp.asarray(np.random.randint(0, 50, (1, 8)), jnp.int32)
        params, state = layer.init(KEY, ids.shape)
        out1, _ = layer.call(params, state, ids)
        ids2 = ids.at[0, 7].set((int(ids[0, 7]) + 1) % 50)
        out2, _ = layer.call(params, state, ids2)
        np.testing.assert_allclose(np.asarray(out1[:, :7]),
                                   np.asarray(out2[:, :7]),
                                   rtol=1e-4, atol=1e-4)

    def test_bert_outputs(self):
        layer = BERT(vocab=60, hidden_size=32, n_block=2, nhead=2,
                     intermediate_size=64, max_position_len=16)
        ids = jnp.asarray(np.random.randint(0, 60, (2, 10)), jnp.int32)
        segs = jnp.zeros_like(ids)
        params, state = layer.init(KEY, ids.shape, segs.shape)
        (seq, pooled), _ = layer.call(params, state, ids, segs)
        assert seq.shape == (2, 10, 32)
        assert pooled.shape == (2, 32)
        assert np.abs(np.asarray(pooled)).max() <= 1.0  # tanh pooler

    def test_bert_mask_ignores_padding(self):
        layer = BERT(vocab=30, hidden_size=16, n_block=1, nhead=2,
                     intermediate_size=32, max_position_len=8,
                     hidden_drop=0.0, attn_drop=0.0)
        ids = jnp.asarray(np.random.randint(1, 30, (1, 8)), jnp.int32)
        segs = jnp.zeros_like(ids)
        mask = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]], jnp.float32)
        params, state = layer.init(KEY, ids.shape, segs.shape)
        (seq1, _), _ = layer.call(params, state, ids, segs, None, mask)
        ids2 = ids.at[0, 6].set((int(ids[0, 6]) + 5) % 30)
        (seq2, _), _ = layer.call(params, state, ids2, segs, None, mask)
        np.testing.assert_allclose(np.asarray(seq1[:, :4]),
                                   np.asarray(seq2[:, :4]),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("what", ["forward", "gradient"])
    def test_projection_names_lower_to_nothing(self, monkeypatch, what):
        """The ``checkpoint_name``s on the projections' results are read by
        the looped stack's ``jax.checkpoint`` alone: a block under none
        lowers to the program it was without them (the lowering numbers
        its private functions by the equations before them; no more
        differs)."""
        import re

        from analytics_zoo_tpu.nn.layers import attention

        blk = TransformerBlock(2, 32, 64, hidden_drop=0.0, attn_drop=0.0,
                               name="inert")
        x = jax.random.normal(KEY, (2, 8, 32))
        params = blk.build_params(KEY, x.shape)

        def traced():
            """(jaxpr, lowered text) of a function made anew, so that no
            earlier trace of it is found again."""
            def fn(p, h):
                return blk.forward(p, h)

            if what == "gradient":
                fn = jax.grad(lambda p, h: jnp.sum(jnp.square(
                    blk.forward(p, h))))
            return (str(jax.make_jaxpr(fn)(params, x)),
                    re.sub(r"@(\w+?)_\d+\b", r"@\1",
                           jax.jit(fn).lower(params, x).as_text()))

        jaxpr, named = traced()
        assert "name[name=q]" in jaxpr
        monkeypatch.setattr(attention, "checkpoint_name", lambda v, name: v)
        jaxpr, plain = traced()
        assert "name[name=" not in jaxpr
        assert named == plain

    def test_unrolled_bert_computes_nothing_again(self):
        layer = BERT(vocab=30, hidden_size=16, n_block=2, nhead=2,
                     intermediate_size=32, max_position_len=8,
                     hidden_drop=0.0, attn_drop=0.0)
        ids = jnp.asarray(np.random.randint(1, 30, (1, 8)), jnp.int32)
        params, _ = layer.init(KEY, ids.shape, ids.shape)
        jaxpr = str(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
            layer.forward(p, ids, jnp.zeros_like(ids))[0])))(params))
        assert "checkpoint" not in jaxpr and "remat" not in jaxpr

    def test_transformer_trains_in_sequential(self):
        from analytics_zoo_tpu.nn import Sequential
        from analytics_zoo_tpu.nn.layers.core import Dense
        from analytics_zoo_tpu.nn.layers.pooling import GlobalAveragePooling1D
        from analytics_zoo_tpu.train.optimizers import Adam

        model = Sequential([
            TransformerLayer(vocab=20, seq_len=6, n_block=1, nhead=2,
                             hidden_size=16, input_shape=(6,)),
            GlobalAveragePooling1D(),
            Dense(2),
        ])
        model.compile(optimizer=Adam(1e-2),
                      loss="sparse_categorical_crossentropy_with_logits",
                      metrics=["accuracy"])
        rs = np.random.RandomState(0)
        x = rs.randint(0, 20, (32, 6)).astype(np.int32)
        y = (x[:, 0] > 9).astype(np.int32)
        model.fit(x, y, batch_size=16, nb_epoch=8, verbose=False)
        res = model.evaluate(x, y, batch_size=16)
        assert res["accuracy"] > 0.8, res


class TestFlashBackwardKernel:
    """The hand-written Pallas backward (dQ/dKV kernels, FA-2 recipe)
    must match autodiff through the reference implementation."""

    def _grads(self, fn, q, k, v):
        import jax
        import jax.numpy as jnp

        def loss(q_, k_, v_):
            out = fn(q_, k_, v_)
            return jnp.sum(out * jnp.cos(out))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_bwd_matches_reference(self, causal):
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.attention import reference_attention
        from analytics_zoo_tpu.ops.flash_attention import flash_attention

        rs = np.random.RandomState(0)
        shape = (1, 2, 256, 128)
        q = jnp.asarray(rs.randn(*shape).astype(np.float32) * 0.5)
        k = jnp.asarray(rs.randn(*shape).astype(np.float32) * 0.5)
        v = jnp.asarray(rs.randn(*shape).astype(np.float32) * 0.5)

        g_flash = self._grads(
            lambda a, b, c: flash_attention(a, b, c, causal,
                                            None, 128, 128, True),
            q, k, v)
        g_ref = self._grads(
            lambda a, b, c: reference_attention(a, b, c, causal=causal),
            q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       rtol=2e-3, atol=2e-4, err_msg=name)

    def test_bwd_cross_attention_lengths(self):
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.attention import reference_attention
        from analytics_zoo_tpu.ops.flash_attention import flash_attention

        rs = np.random.RandomState(1)
        q = jnp.asarray(rs.randn(1, 1, 128, 128).astype(np.float32) * 0.5)
        k = jnp.asarray(rs.randn(1, 1, 384, 128).astype(np.float32) * 0.5)
        v = jnp.asarray(rs.randn(1, 1, 384, 128).astype(np.float32) * 0.5)
        g_flash = self._grads(
            lambda a, b, c: flash_attention(a, b, c, False,
                                            None, 128, 128, True), q, k, v)
        g_ref = self._grads(
            lambda a, b, c: reference_attention(a, b, c), q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       rtol=2e-3, atol=2e-4, err_msg=name)

    def test_fwd_lse_consistent(self):
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.flash_attention import _flash_fwd

        rs = np.random.RandomState(2)
        q = jnp.asarray(rs.randn(1, 1, 128, 128).astype(np.float32) * 0.5)
        k = jnp.asarray(rs.randn(1, 1, 128, 128).astype(np.float32) * 0.5)
        v = jnp.asarray(rs.randn(1, 1, 128, 128).astype(np.float32) * 0.5)
        scale = 1.0 / (128 ** 0.5)
        out, lse = _flash_fwd(q, k, v, scale, False, 128, 128, True,
                              with_lse=True)
        # oracle lse
        s = (q * scale) @ k.swapaxes(-1, -2)
        ref_lse = jax.nn.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the flash kernels' tiling (PR 30): operands in the input's dtype, tiles of
# unequal sides, the mask on crossed tiles only, dead tiles not fetched
# ---------------------------------------------------------------------------

def _flash_module():
    # ``analytics_zoo_tpu.ops.flash_attention`` names the function: the
    # package re-exports it over the module
    import importlib

    return importlib.import_module("analytics_zoo_tpu.ops.flash_attention")


def _flash_case(dtype, lq, lk, seed):
    rs = np.random.RandomState(seed)
    q, g = (jnp.asarray(rs.randn(1, 2, lq, 128) * 0.5, dtype)
            for _ in range(2))
    k, v = (jnp.asarray(rs.randn(1, 2, lk, 128) * 0.5, dtype)
            for _ in range(2))
    return q, k, v, g


def _oracle(q, k, v, g, causal, dtype=jnp.float32):
    """out, lse and the three gradients by ``reference_attention`` with
    the (already rounded) inputs taken to ``dtype``."""
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    out, vjp = jax.vjp(
        lambda a, b, c: reference_attention(a, b, c, causal=causal), q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) \
        / np.sqrt(q.shape[-1])
    if causal:
        lq, lk = q.shape[2], k.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq), s,
                      -jnp.inf)
    return (out, jax.nn.logsumexp(s, axis=-1)) + tuple(vjp(g.astype(dtype)))


def _gap(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _flash_all(q, k, v, g, causal, bq, bk):
    from analytics_zoo_tpu.ops.flash_attention import _flash_bwd, _flash_fwd

    scale = 1.0 / np.sqrt(q.shape[-1])
    out, lse = _flash_fwd(q, k, v, scale, causal, bq, bk, True,
                          with_lse=True)
    return (out, lse) + _flash_bwd(q, k, v, out, lse, g, scale, causal,
                                   bq, bk, True)


def _tile_kinds_of(lq, lk, bq, bk):
    """How many score tiles of a causal call are crossed, full, dead."""
    from analytics_zoo_tpu.ops.flash_attention import _tile_kinds

    n = dict(crossed=0, full=0, dead=0)
    for qi in range(lq // bq):
        for ki in range(lk // bk):
            crossed, full = (bool(x) for x in
                             _tile_kinds(qi, ki, bq, bk, lk - lq, True))
            n["crossed" if crossed else "full" if full else "dead"] += 1
    return n


class TestFlashTiling:
    NAMES = ("out", "lse", "dq", "dk", "dv")

    @pytest.mark.parametrize("lq,lk", [(512, 512), (256, 512)])
    @pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128)])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_unequal_tiles_meet_the_reference(self, dtype, causal, bq, bk,
                                              lq, lk):
        q, k, v, g = _flash_case(dtype, lq, lk, seed=lq + bq)
        got = _flash_all(q, k, v, g, causal, bq, bk)
        want = _oracle(q, k, v, g, causal)
        if dtype == jnp.float32:
            for a, b, name in zip(got, want, self.NAMES):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-3, atol=2e-4,
                                           err_msg=name)
            return
        # bfloat16: the kernels (float32 scores, softmax and sums) may be
        # no further from the float32 answer than plain bfloat16 math is
        plain = _oracle(q, k, v, g, causal, jnp.bfloat16)
        for a, b, c, name in zip(got, want, plain, self.NAMES):
            assert a.dtype == (jnp.float32 if name == "lse" else dtype)
            assert _gap(a, b) <= max(_gap(c, b), 1e-6), name

    def test_dead_full_and_crossed_tiles_in_one_call(self):
        lq = lk = 512
        kinds = _tile_kinds_of(lq, lk, 128, 128)
        assert kinds == dict(crossed=4, full=6, dead=6)
        q, k, v, g = _flash_case(jnp.float32, lq, lk, seed=5)
        for a, b, name in zip(_flash_all(q, k, v, g, True, 128, 128),
                              _oracle(q, k, v, g, True), self.NAMES):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4, err_msg=name)

    def test_every_live_tile_crossed(self):
        lq = lk = 256
        assert _tile_kinds_of(lq, lk, 256, 256) == dict(crossed=1, full=0,
                                                        dead=0)
        q, k, v, g = _flash_case(jnp.float32, lq, lk, seed=6)
        for a, b, name in zip(_flash_all(q, k, v, g, True, 256, 256),
                              _oracle(q, k, v, g, True), self.NAMES):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4, err_msg=name)

    def test_rows_that_see_no_key_give_zero(self):
        # causal with lq > lk: the first lq - lk query rows see nothing
        from analytics_zoo_tpu.ops.flash_attention import flash_attention

        q, k, v, g = _flash_case(jnp.float32, 384, 128, seed=7)

        def f(q, k, v):
            return flash_attention(q, k, v, True, None, 128, 128, True)

        out, vjp = jax.vjp(f, q, k, v)
        want, ref_vjp = jax.vjp(
            lambda a, b, c: reference_attention(a, b, c, causal=True),
            q, k, v)
        assert not np.asarray(out[:, :, :256]).any()
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-3, atol=2e-4)
        for a, b, name in zip(vjp(g), ref_vjp(g), "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4, err_msg=name)

    def test_default_tiles_match_explicit_ones(self):
        from analytics_zoo_tpu.ops.flash_attention import flash_attention

        q, k, v, _ = _flash_case(jnp.float32, 256, 256, seed=8)
        a = flash_attention(q, k, v, True, None, None, None, True)
        b = flash_attention(q, k, v, True, None, 256, 256, True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


class TestFlashTileRule:
    """``_tiles`` is a function of shapes alone: held here to what the
    kernels and Mosaic need of it, whatever it prefers."""

    SHAPES = [
        # lq, lk, d, dtype, causal
        (4096, 4096, 128, jnp.bfloat16, True),    # ouro-2.6b-fit-packed4k
        (2048, 2048, 128, jnp.bfloat16, True),    # chip_smoke's
        (2048, 2048, 64, jnp.float32, False),
        (512, 4608, 128, jnp.bfloat16, True),     # a hop's rows, longer keys
        (4608, 4608, 128, jnp.bfloat16, True),
        (2176, 2176, 128, jnp.bfloat16, True),    # 17 x 128: few divisors
        (4096, 4096, 512, jnp.float32, True),     # the budget binds
    ]

    @pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
    @pytest.mark.parametrize("lq,lk,d,dtype,causal", SHAPES)
    def test_tiles_divide_align_and_fit(self, kernel, lq, lk, d, dtype,
                                        causal):
        fa = _flash_module()
        bq, bk = fa._tiles(kernel, lq, lk, d, dtype, causal)
        assert lq % bq == 0 and lk % bk == 0
        assert bq % 128 == 0 and bk % 128 == 0
        need = fa._tile_bytes(kernel, bq, bk, d, jnp.dtype(dtype).itemsize)
        assert need <= fa._VMEM_BUDGET
        params = fa._compiler_params(kernel, bq, bk, d, dtype)
        limit = (params.vmem_limit_bytes if params is not None
                 else fa._VMEM_DEFAULT)
        assert need < limit
        # a larger tile costs more, so the budget can bind
        assert fa._tile_bytes(kernel, 2 * bq, bk, d, 2) > \
            fa._tile_bytes(kernel, bq, bk, d, 2)

    def test_long_sequences_leave_the_old_tile_behind(self):
        fa = _flash_module()
        for kernel in ("fwd", "dq", "dkv"):
            bq, bk = fa._tiles(kernel, 4096, 4096, 128, jnp.bfloat16, True)
            assert bq >= 512 and bk >= 512, (kernel, bq, bk)

    @pytest.mark.parametrize("blocks", [(128, 128), (256, 256), (128, 256),
                                        (256, None), (None, 128)])
    def test_explicit_blocks_are_obeyed(self, blocks):
        fa = _flash_module()
        q = jax.ShapeDtypeStruct((2, 16, 4096, 128), jnp.bfloat16)
        for kernel in ("fwd", "dq", "dkv"):
            *_, bq, bk = fa._blocks(q, q, *blocks, kernel, True)
            rule = fa._tiles(kernel, 4096, 4096, 128, jnp.bfloat16, True)
            assert bq == (blocks[0] or rule[0])
            assert bk == (blocks[1] or rule[1])

    def test_a_short_or_ragged_length_goes_whole(self):
        fa = _flash_module()
        assert fa._tiles("fwd", 64, 200, 128, jnp.float32, False) == (64, 200)


class TestFlashCompilesForTheChip:
    """Mosaic's own answer, with no chip (tests/mosaic_aot.py): the tiles
    the rule picks, under the VMEM limit it sets, are accepted at the real
    shapes."""

    @pytest.mark.parametrize("b,h,l,d,dtype,causal", [
        (2, 16, 4096, 128, jnp.bfloat16, True),
        (2, 8, 2048, 128, jnp.float32, False),
        (1, 8, 2176, 128, jnp.bfloat16, True),
    ])
    def test_forward_and_backward(self, b, h, l, d, dtype, causal):
        from analytics_zoo_tpu.ops.flash_attention import (_flash_bwd,
                                                           _flash_fwd)
        from tests.mosaic_aot import spec, tpu_compile

        x = spec((b, h, l, d), dtype)
        rows = spec((b, h, l), jnp.float32)
        scale = d ** -0.5
        tpu_compile(lambda q, k, v: _flash_fwd(
            q, k, v, scale, causal, None, None, False, with_lse=True),
            x, x, x)
        text = tpu_compile(lambda q, k, v, o, lse, g: _flash_bwd(
            q, k, v, o, lse, g, scale, causal, None, None, False),
            x, x, x, x, rows, x).as_text()
        assert "flash_attention_dq" in text and "flash_attention_dkv" in text
