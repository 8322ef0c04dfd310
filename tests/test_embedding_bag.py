"""Fused embedding-bag kernel: parity, grads, dispatch, zero transfers.

The Pallas kernel (``ops/embedding_bag.py``) runs here in interpreter
mode on CPU — the same kernel program the TPU executes, minus the
hardware — and must match the pure-JAX oracle at rtol 1e-6 for BOTH the
forward and the hand-written scatter backward, across the ragged shapes
the recommenders actually feed it (bag length 1, bag counts that don't
fill the 8-bag grid block, pad-id conventions, tables that don't tile).

The layer-level tests prove the wiring is transparent: ``Embedding`` /
``EmbeddingBag`` / ``SparseEmbedding`` route through the kernel's
dispatcher without changing a single output, and the whole fused path
moves zero implicit host<->device bytes per batch (transfer_guard).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops import dispatch
from analytics_zoo_tpu.ops.embedding_bag import (
    COMBINERS,
    embedding_bag,
    embedding_bag_reference,
    embedding_gather,
)

RTOL = 1e-6


def _mk(v, d, b, n, seed=0, lo=0, hi=None):
    rs = np.random.RandomState(seed)
    table = jnp.asarray(rs.randn(v, d).astype(np.float32))
    ids = jnp.asarray(rs.randint(lo, hi if hi is not None else v,
                                 size=(b, n)).astype(np.int32))
    return table, ids


class TestForwardParity:
    @pytest.mark.parametrize("combiner", COMBINERS)
    def test_combiners_match_reference(self, combiner):
        table, ids = _mk(512, 16, 12, 5)
        got = embedding_bag(table, ids, combiner, pad_id=0, interpret=True)
        want = embedding_bag_reference(table, ids, combiner, pad_id=0)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=1e-6)

    @pytest.mark.parametrize("b,n", [(1, 1), (7, 3), (8, 1), (9, 17)])
    def test_ragged_bag_shapes(self, b, n):
        # bag counts off the 8-bag grid block, single-slot bags
        table, ids = _mk(300, 24, b, n, seed=b * 31 + n)
        got = embedding_bag(table, ids, "mean", pad_id=0, interpret=True)
        want = embedding_bag_reference(table, ids, "mean", pad_id=0)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=1e-6)

    def test_table_off_tile_sizes(self):
        # vocab/dim that are not multiples of any lane/sublane tile
        table, ids = _mk(1001, 13, 10, 4)
        got = embedding_bag(table, ids, "sum", pad_id=None, interpret=True)
        want = embedding_bag_reference(table, ids, "sum", pad_id=None)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=1e-6)

    def test_negative_pad_id(self):
        table, ids = _mk(128, 8, 6, 4, lo=-1)     # -1 marks empty slots
        got = embedding_bag(table, ids, "sum", pad_id=-1, interpret=True)
        want = embedding_bag_reference(table, ids, "sum", pad_id=-1)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=1e-6)

    def test_fully_padded_bag_is_zero(self):
        table, ids = _mk(64, 8, 4, 3)
        ids = ids.at[2].set(-1)
        out = embedding_bag(table, ids, "mean", pad_id=-1, interpret=True)
        ref = embedding_bag_reference(table, ids, "mean", pad_id=-1)
        np.testing.assert_allclose(out, ref, rtol=RTOL, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out[2]),
                                      np.zeros(8, np.float32))

    def test_bad_combiner_rejected(self):
        table, ids = _mk(32, 4, 2, 2)
        with pytest.raises(ValueError, match="combiner"):
            embedding_bag(table, ids, "max")


class TestGradParity:
    @pytest.mark.parametrize("combiner", COMBINERS)
    def test_dtable_matches_reference(self, combiner):
        table, ids = _mk(100, 12, 5, 3, seed=7)

        def loss(fn):
            def f(t):
                out = fn(t, ids, combiner, 0)
                return jnp.sum(out * out)    # non-uniform cotangent
            return f

        g_kernel = jax.grad(loss(
            lambda t, i, c, p: embedding_bag(t, i, c, p,
                                             interpret=True)))(table)
        g_ref = jax.grad(loss(embedding_bag_reference))(table)
        np.testing.assert_allclose(g_kernel, g_ref, rtol=RTOL, atol=1e-6)

    def test_repeated_ids_accumulate(self):
        # the scatter must ACCUMULATE when one row appears in many bags
        table, _ = _mk(50, 8, 1, 1)
        ids = jnp.zeros((8, 4), jnp.int32) + 3     # every slot row 3
        g = jax.grad(lambda t: jnp.sum(
            embedding_bag(t, ids, "sum", None, interpret=True)))(table)
        np.testing.assert_allclose(np.asarray(g[3]),
                                   np.full(8, 32.0, np.float32),
                                   rtol=RTOL)
        assert float(jnp.abs(g[4]).max()) == 0.0


class TestDedup:
    """Within-batch duplicate-id dedup (ISSUE 19): the static-shape
    unique-before-gather path must match the naive lookup EXACTLY —
    forward and per-occurrence gradient — including the degenerate
    batches dedup exists for (every slot one id) and the ones that
    could break the inverse-index scatter (fully padded bags)."""

    @pytest.mark.parametrize("combiner", COMBINERS)
    def test_forward_matches_reference(self, combiner):
        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag_dedup

        table, ids = _mk(512, 16, 12, 5)
        got = embedding_bag_dedup(table, ids, combiner, pad_id=0)
        want = embedding_bag_reference(table, ids, combiner, pad_id=0)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=1e-6)

    def test_no_pad_id_counts_every_slot(self):
        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag_dedup

        table, ids = _mk(128, 8, 6, 4, seed=3)
        got = embedding_bag_dedup(table, ids, "mean", pad_id=None)
        want = embedding_bag_reference(table, ids, "mean", pad_id=None)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=1e-6)

    @pytest.mark.parametrize("combiner", COMBINERS)
    def test_grad_per_occurrence_accumulation(self, combiner):
        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag_dedup

        table, ids = _mk(100, 12, 5, 3, seed=7)

        def loss(fn):
            return lambda t: jnp.sum(fn(t, ids, combiner, 0) ** 2)

        g_d = jax.grad(loss(embedding_bag_dedup))(table)
        g_r = jax.grad(loss(embedding_bag_reference))(table)
        np.testing.assert_allclose(g_d, g_r, rtol=RTOL, atol=1e-6)

    def test_fully_duplicated_batch(self):
        # the motivating regression: EVERY slot the same id — unique
        # collapses to one live row; forward and grad must still match
        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag_dedup

        table, _ = _mk(64, 8, 1, 1)
        ids = jnp.full((16, 4), 5, jnp.int32)
        got = embedding_bag_dedup(table, ids, "sum", pad_id=None)
        want = embedding_bag_reference(table, ids, "sum", pad_id=None)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=1e-6)
        g = jax.grad(lambda t: jnp.sum(
            embedding_bag_dedup(t, ids, "sum", None)))(table)
        # 64 occurrences of row 5 -> gradient 64 per feature, all at 5
        np.testing.assert_allclose(np.asarray(g[5]),
                                   np.full(8, 64.0, np.float32),
                                   rtol=RTOL)
        assert float(jnp.abs(g[4]).max()) == 0.0

    def test_all_pad_bag_is_zero_with_zero_grad(self):
        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag_dedup

        table, ids = _mk(64, 8, 4, 3)
        ids = ids.at[2].set(-1)               # one fully-padded bag
        out = embedding_bag_dedup(table, ids, "mean", pad_id=-1)
        ref = embedding_bag_reference(table, ids, "mean", pad_id=-1)
        np.testing.assert_allclose(out, ref, rtol=RTOL, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out[2]),
                                      np.zeros(8, np.float32))
        # an ALL-pad batch: the pad key unifies with the unique fill
        # tail, so no live row exists and the grad is exactly zero
        all_pad = jnp.full((4, 3), -1, jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(embedding_bag_dedup(table, all_pad, "sum", -1)),
            np.zeros((4, 8), np.float32))
        g = jax.grad(lambda t: jnp.sum(
            embedding_bag_dedup(t, all_pad, "sum", -1)))(table)
        assert float(jnp.abs(g).max()) == 0.0

    def test_jit_and_vmap_safe(self):
        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag_dedup

        table, ids = _mk(64, 8, 6, 4, seed=9)
        got = jax.jit(lambda t, i: embedding_bag_dedup(
            t, i, "sum", 0))(table, ids)
        want = embedding_bag_reference(table, ids, "sum", 0)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=1e-6)

    def test_dedup_wanted_knob_resolution(self):
        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.ops.embedding_bag import dedup_wanted

        try:
            init_zoo_context(dedup_ids="off")
            assert dedup_wanted(sharded=True) is False
            init_zoo_context(dedup_ids="on")
            assert dedup_wanted(sharded=False) is True
        finally:
            init_zoo_context()
        # auto: on for the sharded path (dedup shrinks the exchange),
        # off for the dense path (the gather is already local)
        assert dedup_wanted(sharded=True) is True
        assert dedup_wanted(sharded=False) is False

    def test_selection_metric_recorded(self):
        from analytics_zoo_tpu.observe.metrics import METRICS
        from analytics_zoo_tpu.ops.embedding_bag import dedup_wanted

        before = METRICS.snapshot()
        dedup_wanted(sharded=True)
        key = ("table_dedup_selected_total",
               (("decision", "on"), ("reason", "auto_sharded")))
        got = METRICS.snapshot().counters.get(key, 0)
        assert got == before.counters.get(key, 0) + 1


class TestEmbeddingGather:
    def test_matrix_ids_match_take(self):
        table, ids = _mk(256, 10, 6, 7)
        got = embedding_gather(table, ids, interpret=True)
        np.testing.assert_allclose(got, jnp.take(table, ids, axis=0),
                                   rtol=RTOL, atol=1e-6)

    def test_vector_ids_keep_shape(self):
        table, _ = _mk(100, 6, 1, 1)
        ids = jnp.asarray([0, 5, 99, 5], jnp.int32)
        got = embedding_gather(table, ids, interpret=True)
        assert got.shape == (4, 6)
        np.testing.assert_allclose(got, table[ids], rtol=RTOL, atol=1e-6)

    def test_gather_grad(self):
        table, ids = _mk(64, 4, 3, 3, seed=2)
        g_k = jax.grad(lambda t: jnp.sum(
            embedding_gather(t, ids, interpret=True) ** 2))(table)
        g_r = jax.grad(lambda t: jnp.sum(
            jnp.take(t, ids, axis=0) ** 2))(table)
        np.testing.assert_allclose(g_k, g_r, rtol=RTOL, atol=1e-6)


class TestDispatch:
    def test_reference_on_cpu(self):
        # no TPU backend in tier-1: auto must route to the oracle
        assert dispatch.select_path("embedding_bag") == \
            dispatch.PATH_REFERENCE

    def test_knob_off_beats_min_work(self):
        assert dispatch.select_path(
            "embedding_bag", min_work_met=True,
            knob="off") == dispatch.PATH_REFERENCE

    def test_force_interpret_wins(self):
        assert dispatch.select_path(
            "embedding_bag", knob="off",
            force=dispatch.PATH_INTERPRET) == dispatch.PATH_INTERPRET

    def test_bad_force_rejected(self):
        with pytest.raises(ValueError, match="unknown kernel path"):
            dispatch.select_path("embedding_bag", force="gpu")

    def test_selection_metric_recorded(self):
        from analytics_zoo_tpu.observe.metrics import METRICS
        before = METRICS.snapshot()
        dispatch.select_path("embedding_bag", knob="off")
        key = ("ops_kernel_selected_total",
               (("kernel", "embedding_bag"), ("path", "reference")))
        got = METRICS.snapshot().counters.get(key, 0)
        assert got == before.counters.get(key, 0) + 1

    def test_fused_embedding_knob_reaches_dispatch(self):
        from analytics_zoo_tpu import init_zoo_context
        try:
            init_zoo_context(fused_embedding="off")
            assert dispatch.config_knob("fused_embedding", "auto") == "off"
        finally:
            init_zoo_context()
        assert dispatch.config_knob("fused_embedding", "auto") == "auto"


class TestMosaicShapeRules:
    """``_mosaic_accepts``: auto-dispatch on TPU selects the compiled
    kernel only where Mosaic accepts it (float32 rows of exactly 128
    lanes, bags of at most 128 ids).  Each rule has a case on each side:
    the path and counted series with ``on_tpu`` patched to true, and the
    TPU compiler's own verdict on the same shape (tests/mosaic_aot.py)."""

    # (V, D, table dtype, N, kernel path selected?)
    CASES = [
        pytest.param(4096, 128, jnp.float32, 4, True, id="f32-d128"),
        pytest.param(4096, 64, jnp.float32, 4, False, id="narrow-row"),
        pytest.param(6041, 20, jnp.float32, 4, False, id="ncf-row"),
        pytest.param(4096, 256, jnp.float32, 4, False, id="wide-row"),
        pytest.param(4096, 128, jnp.bfloat16, 4, False, id="bf16-row"),
        pytest.param(4096, 128, jnp.float32, 128, True, id="n128"),
        pytest.param(4096, 128, jnp.float32, 129, False, id="n129"),
    ]

    @pytest.mark.parametrize("v,d,dtype,n,kernel", CASES)
    def test_bag_path_and_series(self, monkeypatch, v, d, dtype, n, kernel):
        from tests.mosaic_aot import selected_series, series

        got = selected_series(
            monkeypatch, lambda t, i: embedding_bag(t, i, "sum", 0),
            jax.ShapeDtypeStruct((v, d), dtype),
            jax.ShapeDtypeStruct((8, n), jnp.int32))
        assert got == series("embedding_bag",
                             "pallas" if kernel else "reference")

    @pytest.mark.parametrize("d,dtype,kernel", [
        (128, jnp.float32, True), (20, jnp.float32, False),
        (128, jnp.bfloat16, False)])
    def test_gather_path_and_series(self, monkeypatch, d, dtype, kernel):
        from tests.mosaic_aot import selected_series, series

        got = selected_series(
            monkeypatch, embedding_gather,
            jax.ShapeDtypeStruct((6041, d), dtype),
            jax.ShapeDtypeStruct((64, 1), jnp.int32))
        assert got == series("embedding_gather",
                             "pallas" if kernel else "reference")

    def test_small_table_stays_on_reference(self, monkeypatch):
        # min_work_met, not a shape rule: a table XLA keeps in cache
        from tests.mosaic_aot import selected_series, series

        got = selected_series(
            monkeypatch, embedding_gather,
            jax.ShapeDtypeStruct((4095, 128), jnp.float32),
            jax.ShapeDtypeStruct((64, 1), jnp.int32))
        assert got == series("embedding_gather", "reference")

    @staticmethod
    def _kernel_fwd_bwd(n):
        from analytics_zoo_tpu.ops.embedding_bag import _bag

        def loss(t, i):
            return _bag(t, i, "mean", 0, False).astype(jnp.float32).sum()

        return jax.value_and_grad(loss)

    def test_mosaic_compiles_the_selected_shape(self):
        from tests.mosaic_aot import spec, tpu_compile

        tpu_compile(self._kernel_fwd_bwd(4), spec((4096, 128), jnp.float32),
                    spec((16, 4), jnp.int32))

    @pytest.mark.parametrize("d,dtype,why", [
        (64, jnp.float32, r"dimension 1 must be aligned to tiling \(128\)"),
        (256, jnp.float32, r"dimension 0 must be aligned to tiling \(8\)"),
        (128, jnp.bfloat16, r"dimension 0 must be aligned to tiling \(8\)"),
    ])
    def test_mosaic_refuses_the_excluded_rows(self, d, dtype, why):
        from tests.mosaic_aot import spec, tpu_compile

        with pytest.raises(Exception, match=why):
            tpu_compile(lambda t, i: self._kernel_fwd_bwd(4)(t, i)[0],
                        spec((4096, d), dtype), spec((16, 4), jnp.int32))

    @pytest.mark.slow
    def test_mosaic_bag_length_limit(self):
        # the unrolled per-row DMAs make these compiles take seconds
        from analytics_zoo_tpu.ops.embedding_bag import _bag
        from tests.mosaic_aot import spec, tpu_compile

        fwd = lambda t, i: _bag(t, i, "sum", 0, False)
        tpu_compile(fwd, spec((4096, 128), jnp.float32),
                    spec((8, 128), jnp.int32))
        with pytest.raises(Exception, match="sflag"):
            tpu_compile(fwd, spec((4096, 128), jnp.float32),
                        spec((8, 256), jnp.int32))

    def test_interpret_parity_at_smallest_compiled_shape(self):
        # one 128-lane row per id, one 8-bag block, forward and backward
        table, ids = _mk(4096, 128, 8, 1, seed=11)

        def loss(f):
            return lambda t: jnp.sum(f(t) ** 2)

        kern = lambda t: embedding_gather(t, ids, interpret=True)
        ref = lambda t: jnp.take(t, ids, axis=0)
        np.testing.assert_allclose(kern(table), ref(table), rtol=RTOL)
        np.testing.assert_allclose(jax.grad(loss(kern))(table),
                                   jax.grad(loss(ref))(table), rtol=RTOL,
                                   atol=1e-6)


class TestLayerWiring:
    def test_embedding_layer_output_unchanged(self, rng):
        from analytics_zoo_tpu.nn.layers.embedding import Embedding

        layer = Embedding(40, 6, name="emb_kernel_wire")
        params = layer.build_params(rng, (4, 3))
        ids = jnp.asarray([[1, 2, 3], [0, 0, 39], [5, 6, 7], [9, 9, 9]],
                          jnp.int32)
        out = layer.forward(params, ids)
        np.testing.assert_allclose(
            out, jnp.take(params["table"], ids, axis=0), rtol=RTOL)

    def test_embedding_bag_layer_matches_reference(self, rng):
        from analytics_zoo_tpu.nn.layers.embedding import EmbeddingBag

        layer = EmbeddingBag(30, 5, combiner="mean", pad_id=0,
                             name="bag_kernel_wire")
        params = layer.build_params(rng, (2, 4))
        ids = jnp.asarray([[1, 2, 0, 0], [3, 0, 0, 0]], jnp.int32)
        out = layer.forward(params, ids)
        want = embedding_bag_reference(params["table"], ids, "mean", 0)
        np.testing.assert_allclose(out, want, rtol=RTOL, atol=1e-6)
        # pad row zeroed at init so padding can't leak through "sum"
        assert float(jnp.abs(params["table"][0]).max()) == 0.0

    @pytest.mark.transfer_guard
    def test_fused_path_moves_zero_host_bytes_per_batch(self):
        """The per-batch hot loop — ids in, bag vectors out — must not
        trigger a single implicit host<->device transfer.  Explicit
        device_put of the batch is the ONLY transfer; everything after
        runs under ``jax.transfer_guard("disallow")``."""
        from analytics_zoo_tpu.nn.layers.embedding import EmbeddingBag

        layer = EmbeddingBag(64, 8, combiner="sum", pad_id=None,
                             name="bag_guard_wire")
        with jax.transfer_guard("allow"):   # setup is not the hot path
            params = jax.device_put(
                layer.build_params(jax.random.PRNGKey(0), (8, 4)))
            batches = [jax.device_put(
                np.random.RandomState(seed).randint(
                    0, 64, size=(8, 4)).astype(np.int32))
                for seed in range(3)]
        step = jax.jit(lambda p, i: jnp.sum(layer.forward(p, i), axis=-1))
        for ids in batches:         # several batches, zero transfers
            out = step(params, ids)
            assert out.shape == (8,)

    def test_wide_and_deep_wide_tower_uses_bag(self, rng, zoo_ctx):
        """The wide tower's gather-then-Lambda-sum was replaced by an
        EmbeddingBag — same math, one fused lookup."""
        from analytics_zoo_tpu.models import WideAndDeep
        from analytics_zoo_tpu.nn import reset_name_scope
        from analytics_zoo_tpu.nn.layers.embedding import EmbeddingBag

        reset_name_scope()
        wnd = WideAndDeep(class_num=2, model_type="wide",
                          wide_base_dims=(4,), wide_cross_dims=(5,))
        net = wnd.model
        bag = {layer.name: layer for layer in net.layers}["wide_linear"]
        assert isinstance(bag, EmbeddingBag)
        params, state = net.build(rng)
        assert "wide_linear" in params
        x = jnp.asarray([[0, 1], [3, 4], [2, 0]], jnp.int32)
        out, _ = net.call(params, state, x, training=False)
        assert out.shape == (3, 2)
        assert np.all(np.isfinite(np.asarray(out)))
