"""Sharded giant-embedding tables (ISSUE 14).

Covers the whole subsystem on the dryrun dp×tp mesh, fast and in
tier-1:

- the sharded lookup (``parallel.table_sharding.sharded_bag/gather``)
  against the dense reference for every combiner, with gradients;
- the per-table placement router: decisions, downgrade reasons, and the
  ``table_placement_selected_total{placement,reason}`` counter contract;
- ``ShardedEmbeddingTable``: dense fallback off-mesh, sharded lowering
  under an active ``TableShardedStrategy``, name-gated;
- NeuralCF / WideAndDeep with ``table_placement`` — sharded-vs-
  replicated training parity at rtol 1e-6 under the transfer guard
  (zero per-batch host transfers in the hot loop);
- checkpoint topology changes: a 2-way-sharded snapshot restores at
  1-way and 4-way bit-exactly, and the elastic-growth restore (more
  rows than the snapshot) keeps snapshot rows bit-exact while new rows
  keep their fresh initialization;
- the lazy ``SyntheticGiantTable`` fixture: header-only accounting,
  (seed, row)-determinism independent of the slice it was read through.
"""

import json
import shutil

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def fresh_names():
    from analytics_zoo_tpu.nn import reset_name_scope

    reset_name_scope()


@pytest.fixture
def tp_ctx():
    """4×2 data×model dryrun mesh; restores the default afterwards."""
    from analytics_zoo_tpu import init_zoo_context

    ctx = init_zoo_context(mesh_shape=(4, 2),
                           axis_names=("data", "model"))
    yield ctx
    init_zoo_context()


# ---------------------------------------------------------------------------
# the sharded lookup primitive
# ---------------------------------------------------------------------------


class TestShardedLookup:
    @pytest.mark.parametrize("combiner", ["sum", "mean", "sqrtn"])
    def test_bag_matches_dense_reference(self, tp_ctx, combiner):
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag
        from analytics_zoo_tpu.parallel import sharded_bag

        rs = np.random.RandomState(0)
        table = jnp.asarray(rs.randn(48, 8).astype(np.float32))
        ids = jnp.asarray(rs.randint(0, 48, (16, 5)).astype(np.int32))
        ids = ids.at[0, :3].set(0)           # several pad slots
        ref = np.asarray(embedding_bag(table, ids, combiner, pad_id=0))
        got = np.asarray(sharded_bag(table, ids, combiner, pad_id=0,
                                     mesh=tp_ctx.mesh, axis="model"))
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)

    def test_bag_without_pad_counts_every_slot(self, tp_ctx):
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag
        from analytics_zoo_tpu.parallel import sharded_bag

        rs = np.random.RandomState(1)
        table = jnp.asarray(rs.randn(64, 4).astype(np.float32))
        ids = jnp.asarray(rs.randint(0, 64, (8, 7)).astype(np.int32))
        ref = np.asarray(embedding_bag(table, ids, "mean", pad_id=None))
        got = np.asarray(sharded_bag(table, ids, "mean", pad_id=None,
                                     mesh=tp_ctx.mesh, axis="model"))
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)

    def test_gather_matches_take(self, tp_ctx):
        import jax.numpy as jnp

        from analytics_zoo_tpu.parallel import sharded_gather

        rs = np.random.RandomState(2)
        table = jnp.asarray(rs.randn(48, 6).astype(np.float32))
        ids = jnp.asarray(rs.randint(0, 48, (8, 3)).astype(np.int32))
        ref = np.asarray(jnp.take(table, ids, axis=0))
        got = np.asarray(sharded_gather(table, ids, mesh=tp_ctx.mesh,
                                        axis="model"))
        assert got.shape == (8, 3, 6)
        np.testing.assert_array_equal(got, ref)

    def test_gradient_matches_dense(self, tp_ctx):
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag
        from analytics_zoo_tpu.parallel import sharded_bag

        rs = np.random.RandomState(3)
        table = jnp.asarray(rs.randn(48, 8).astype(np.float32))
        ids = jnp.asarray(rs.randint(0, 48, (16, 4)).astype(np.int32))

        def loss_sharded(t):
            out = sharded_bag(t, ids, "sum", pad_id=0,
                              mesh=tp_ctx.mesh, axis="model")
            return jnp.sum(out ** 2)

        def loss_dense(t):
            return jnp.sum(embedding_bag(t, ids, "sum", pad_id=0) ** 2)

        g_s = np.asarray(jax.grad(loss_sharded)(table))
        g_d = np.asarray(jax.grad(loss_dense)(table))
        np.testing.assert_allclose(g_s, g_d, rtol=1e-6, atol=1e-6)

    def test_trivial_mesh_falls_back_to_dense(self, zoo_ctx):
        """On the default ('data',)-only mesh the lookup IS the dense
        ``embedding_bag`` — no shard_map, no collective."""
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag
        from analytics_zoo_tpu.parallel import sharded_bag

        rs = np.random.RandomState(4)
        table = jnp.asarray(rs.randn(32, 4).astype(np.float32))
        ids = jnp.asarray(rs.randint(0, 32, (4, 3)).astype(np.int32))
        ref = np.asarray(embedding_bag(table, ids, "sum", None))
        got = np.asarray(sharded_bag(table, ids, "sum", None,
                                     mesh=zoo_ctx.mesh, axis="model"))
        np.testing.assert_array_equal(got, ref)


class TestRowMath:
    def test_padded_rows(self):
        from analytics_zoo_tpu.parallel import ROW_ALIGN, padded_rows

        assert ROW_ALIGN == 8
        assert padded_rows(1) == 8
        assert padded_rows(8) == 8
        assert padded_rows(9) == 16
        assert padded_rows(100_000_000) == 100_000_000

    def test_resolve_table_ways(self, tp_ctx):
        from analytics_zoo_tpu.parallel import resolve_table_ways

        assert resolve_table_ways(tp_ctx.mesh, "model", 48) == 2
        assert resolve_table_ways(tp_ctx.mesh, "model", 47) == 1
        assert resolve_table_ways(tp_ctx.mesh, "absent", 48) == 1
        assert resolve_table_ways(None, "model", 48) == 1


# ---------------------------------------------------------------------------
# the placement router
# ---------------------------------------------------------------------------


class TestPlacementRouter:
    def test_decisions_and_counter_labels(self, tp_ctx):
        """Every router decision ticks
        ``table_placement_selected_total{placement,reason}`` with the
        bounded reason vocabulary (docs/OBSERVABILITY.md) — the
        alertable form of a table silently downgrading its placement."""
        from analytics_zoo_tpu.observe import metrics as obs
        from analytics_zoo_tpu.parallel import choose_table_placement

        mark = obs.METRICS.snapshot()
        budget = 1 << 20
        cases = [
            # (nbytes, requested) -> (placement, reason)
            (budget // 2, "auto", "replicated", "fits_budget"),
            (budget + 1, "auto", "sharded", "over_budget"),
            (4 * budget, "auto", "stream", "sharded_over_budget"),
            (budget // 2, "sharded", "sharded", "requested"),
            (4 * budget, "replicated", "replicated", "requested"),
        ]
        for nbytes, req, want_p, want_r in cases:
            d = choose_table_placement(
                nbytes=nbytes, rows=1024, requested=req,
                mesh=tp_ctx.mesh, axis="model", budget_bytes=budget)
            assert (d.placement, d.reason_code) == (want_p, want_r), \
                (nbytes, req)
        snap = obs.METRICS.snapshot()
        for _, _, placement, reason in cases:
            key = ("table_placement_selected_total",
                   (("placement", placement), ("reason", reason)))
            assert snap.counters.get(key, 0) >= \
                mark.counters.get(key, 0) + 1, (placement, reason)

    def test_no_model_axis_downgrades(self, zoo_ctx):
        from analytics_zoo_tpu.parallel import choose_table_placement

        d = choose_table_placement(nbytes=1 << 30, rows=1024,
                                   requested="sharded",
                                   mesh=zoo_ctx.mesh, axis="model",
                                   budget_bytes=1 << 20)
        assert d.placement == "replicated"
        assert d.reason_code == "no_model_axis"

    def test_axis_indivisible_reason(self):
        """A mesh axis that exists but does not divide the padded rows
        reports the distinct reason code."""
        import jax
        from jax.sharding import Mesh

        from analytics_zoo_tpu.parallel import choose_table_placement

        devs = np.array(jax.devices()[:6]).reshape(2, 3)
        mesh = Mesh(devs, ("data", "model"))
        d = choose_table_placement(nbytes=1 << 30, rows=32,
                                   requested="auto", mesh=mesh,
                                   axis="model", budget_bytes=1 << 20)
        assert d.placement == "replicated"
        assert d.reason_code == "axis_indivisible"

    def test_unknown_request_rejected(self, zoo_ctx):
        from analytics_zoo_tpu.parallel import choose_table_placement

        with pytest.raises(ValueError, match="table_placement"):
            choose_table_placement(nbytes=1, rows=8, requested="maybe",
                                   mesh=zoo_ctx.mesh,
                                   budget_bytes=1 << 20)


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------


class TestShardedEmbeddingLayer:
    def test_dense_fallback_matches_embedding(self, zoo_ctx):
        import jax

        from analytics_zoo_tpu.nn.layers import (Embedding,
                                                 ShardedEmbeddingTable)

        rng = jax.random.PRNGKey(0)
        # 31+1 = 32 rows: ROW_ALIGN-exact, so the init draw matches the
        # plain Embedding bit-for-bit
        lyr = ShardedEmbeddingTable(32, 8, name="t")
        ref = Embedding(32, 8, name="t_ref")
        p = lyr.build_params(rng, (4, 2))
        p_ref = ref.build_params(rng, (4, 2))
        np.testing.assert_array_equal(np.asarray(p["table"]),
                                      np.asarray(p_ref["table"]))
        ids = np.random.RandomState(0).randint(0, 32, (4, 2))
        ids = np.asarray(ids, np.int32)
        np.testing.assert_array_equal(
            np.asarray(lyr.forward(p, ids)),
            np.asarray(ref.forward(p_ref, ids)))

    def test_rows_padded_to_topology_invariant_shape(self, zoo_ctx):
        import jax

        from analytics_zoo_tpu.nn.layers import ShardedEmbeddingTable

        lyr = ShardedEmbeddingTable(47, 4, name="t")
        p = lyr.build_params(jax.random.PRNGKey(0), (2,))
        assert p["table"].shape == (48, 4)
        assert lyr.table_rows == 48
        assert lyr.table_nbytes == 48 * 4 * 4

    def test_sharded_lowering_is_name_gated(self, tp_ctx):
        """Only tables LISTED in the active strategy lower to the
        exchange; unlisted ones stay dense even while it is active."""
        import jax

        from analytics_zoo_tpu.nn.layers import ShardedEmbeddingTable
        from analytics_zoo_tpu.parallel import TableShardedStrategy

        lyr = ShardedEmbeddingTable(48, 8, name="listed")
        other = ShardedEmbeddingTable(48, 8, name="unlisted")
        p = lyr.build_params(jax.random.PRNGKey(0), (4, 2))
        po = other.build_params(jax.random.PRNGKey(1), (4, 2))
        ids = np.asarray(
            np.random.RandomState(0).randint(0, 48, (8, 2)), np.int32)
        dense = np.asarray(lyr.forward(p, ids))
        dense_o = np.asarray(other.forward(po, ids))
        strat = TableShardedStrategy(tables=("listed",))
        with strat.activate(tp_ctx.mesh):
            assert lyr._sharding_for_trace() is not None
            assert other._sharding_for_trace() is None
            np.testing.assert_array_equal(
                np.asarray(lyr.forward(p, ids)), dense)
            np.testing.assert_array_equal(
                np.asarray(other.forward(po, ids)), dense_o)
        assert lyr._sharding_for_trace() is None

    def test_strategy_param_shardings_split_only_tables(self, tp_ctx):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu.parallel import TableShardedStrategy

        params = {"emb": {"table": jnp.zeros((48, 8))},
                  "dense": {"kernel": jnp.zeros((8, 4))}}
        strat = TableShardedStrategy(tables=("emb",))
        sh = strat.param_shardings(tp_ctx.mesh, params)
        assert sh["emb"]["table"].spec == P("model", None)
        assert sh["dense"]["kernel"].spec == P()

    def test_ensure_table_sharding_idempotent(self, tp_ctx):
        from analytics_zoo_tpu.parallel import (TableShardedStrategy,
                                                ensure_table_sharding)
        from analytics_zoo_tpu.parallel.sharding import DataParallel

        base = DataParallel()
        s1 = ensure_table_sharding(base, ("a",))
        assert isinstance(s1, TableShardedStrategy)
        s2 = ensure_table_sharding(s1, ("a",))
        assert s2 is s1
        assert ensure_table_sharding(base, ()) is base


# ---------------------------------------------------------------------------
# models: NeuralCF / WideAndDeep with table_placement
# ---------------------------------------------------------------------------


def _pair_data(u_max, i_max, n=64, seed=0):
    rs = np.random.RandomState(seed)
    u = rs.randint(1, u_max + 1, (n, 1)).astype(np.int32)
    i = rs.randint(1, i_max + 1, (n, 1)).astype(np.int32)
    y = rs.randint(0, 2, (n,)).astype(np.int32)
    return u, i, y


class TestRecommendersSharded:
    @pytest.mark.transfer_guard
    def test_ncf_sharded_vs_replicated_training_parity(self, tp_ctx):
        """The acceptance gate: identical training trajectories at rtol
        1e-6 on the dryrun 4×2 mesh, hot loop transfer-guarded (zero
        per-batch host transfers).  31/47 ids -> 32/48 rows, so even
        the initializer draws match and parity is bit-near-exact."""
        from analytics_zoo_tpu.models.recommendation import NeuralCF
        from analytics_zoo_tpu.nn import reset_name_scope

        u, i, y = _pair_data(31, 47)

        def train(placement):
            reset_name_scope()
            m = NeuralCF(31, 47, class_num=2, table_placement=placement)
            m.compile(optimizer="adam",
                      loss="sparse_categorical_crossentropy")
            m.fit([u, i], y, batch_size=16, epochs=2, verbose=False)
            return m, m.predict([u, i], batch_size=16)

        m_rep, p_rep = train("replicated")
        assert m_rep.model._sharded_tables == ()
        m_sh, p_sh = train("sharded")
        assert set(m_sh.model._sharded_tables) == {
            "mlp_user_embed", "mlp_item_embed",
            "mf_user_embed", "mf_item_embed"}
        np.testing.assert_allclose(p_sh, p_rep, rtol=1e-6, atol=1e-7)

    def test_ncf_table_params_and_moments_actually_shard(self, tp_ctx):
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu.models.recommendation import NeuralCF

        u, i, y = _pair_data(31, 47)
        m = NeuralCF(31, 47, class_num=2, table_placement="sharded")
        m.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy")
        m.fit([u, i], y, batch_size=16, epochs=1, verbose=False)
        est = m.estimator
        t = est.params["mlp_user_embed"]["table"]
        assert t.sharding.spec == P("model", None)
        assert t.addressable_shards[0].data.shape[0] == t.shape[0] // 2
        # Adam moments follow the table placement (optimizers.py rule)
        import jax
        moments = [x for x in jax.tree_util.tree_leaves(est.opt_state)
                   if getattr(x, "shape", None) == t.shape]
        assert moments, "no params-shaped Adam moment leaves found"
        for mom in moments:
            assert mom.sharding.spec == P("model", None)

    @pytest.mark.transfer_guard
    def test_wide_and_deep_sharded_parity(self, tp_ctx):
        from analytics_zoo_tpu.models.recommendation import WideAndDeep
        from analytics_zoo_tpu.nn import reset_name_scope

        rs = np.random.RandomState(0)
        n = 64
        wide = np.stack([rs.randint(0, 10, n), 10 + rs.randint(0, 6, n)],
                        axis=1).astype(np.int32)
        emb = np.stack([rs.randint(1, 16, n), rs.randint(1, 32, n)],
                       axis=1).astype(np.int32)
        y = rs.randint(0, 2, (n,)).astype(np.int32)

        def train(placement):
            reset_name_scope()
            # 10+6=16 wide rows and 15+1=16 / 31+1=32 embed rows are all
            # ROW_ALIGN-exact, so dense and sharded layers draw the same
            # initial tables and parity is exact
            m = WideAndDeep(class_num=2, wide_base_dims=(10, 6),
                            embed_in_dims=(15, 31),
                            embed_out_dims=(8, 8),
                            hidden_layers=(16, 8),
                            table_placement=placement)
            m.compile(optimizer="adam",
                      loss="sparse_categorical_crossentropy")
            m.fit([wide, emb], y, batch_size=16, epochs=2, verbose=False)
            return m, m.predict([wide, emb], batch_size=16)

        m_rep, p_rep = train("replicated")
        m_sh, p_sh = train("sharded")
        assert "wide_linear" in m_sh.model._sharded_tables
        np.testing.assert_allclose(p_sh, p_rep, rtol=1e-6, atol=1e-7)

    def test_default_placement_on_plain_mesh_uses_dense_layers(
            self, zoo_ctx):
        """``table_placement`` defaults to auto, which on a mesh with
        no model axis keeps every table on the original dense layers —
        the single-device default stays byte-for-byte what it was."""
        from analytics_zoo_tpu.models.recommendation import NeuralCF
        from analytics_zoo_tpu.nn.layers.embedding import Embedding

        m = NeuralCF(31, 47, class_num=2)
        assert m.model._sharded_tables == ()
        assert m.table_placement == "auto"
        embeds = [l for l in m.model.layers
                  if getattr(l, "name", "").endswith("_embed")]
        assert embeds and all(type(l) is Embedding for l in embeds)

    def test_config_round_trips_table_placement(self, zoo_ctx):
        from analytics_zoo_tpu.models.recommendation import (NeuralCF,
                                                             WideAndDeep)

        m = NeuralCF(31, 47, class_num=2, table_placement="sharded")
        cfg = json.loads(json.dumps(m.config()))
        assert cfg["table_placement"] == "sharded"
        m2 = NeuralCF(**cfg)
        assert m2.model._sharded_tables == m.model._sharded_tables
        w = WideAndDeep(class_num=2, wide_base_dims=(4,),
                        embed_in_dims=(7,), embed_out_dims=(4,),
                        table_placement="replicated")
        cfg_w = json.loads(json.dumps(w.config()))
        assert cfg_w["table_placement"] == "replicated"
        WideAndDeep(**cfg_w)

    def test_invalid_placement_rejected(self, zoo_ctx):
        from analytics_zoo_tpu.models.recommendation import NeuralCF

        with pytest.raises(ValueError, match="table_placement"):
            NeuralCF(31, 47, class_num=2, table_placement="magic")


# ---------------------------------------------------------------------------
# checkpoint topology changes + elastic growth
# ---------------------------------------------------------------------------


def _make_ncf(users=31, items=47, placement="sharded"):
    from analytics_zoo_tpu.models.recommendation import NeuralCF
    from analytics_zoo_tpu.nn import reset_name_scope

    reset_name_scope()
    m = NeuralCF(users, items, class_num=2, table_placement=placement)
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    return m


def _table_leaves(params):
    return {name: np.asarray(sub["table"])
            for name, sub in params.items() if "table" in sub}


class TestTopologyCheckpoint:
    def test_2way_checkpoint_restores_at_1way_and_4way(self, tmp_path):
        """Save with tables sharded 2-ways, restore on a mesh with no
        model axis (1-way) and on a 4-way model axis — bit parity on
        every table and identical eval loss, through the ordinary
        ``tree_put_global`` reshard path."""
        from analytics_zoo_tpu import init_zoo_context

        u, i, y = _pair_data(31, 47)
        try:
            init_zoo_context(mesh_shape=(4, 2),
                             axis_names=("data", "model"))
            m = _make_ncf()
            m.estimator.set_checkpoint(str(tmp_path / "orig"))
            m.fit([u, i], y, batch_size=16, epochs=1, verbose=False)
            saved = _table_leaves(m.estimator.params)
            loss = m.evaluate([u, i], y, batch_size=16)["loss"]

            for shape, axes in (((8,), ("data",)),
                                ((2, 4), ("data", "model"))):
                init_zoo_context(mesh_shape=shape, axis_names=axes)
                m2 = _make_ncf()
                m2.estimator._ensure_built([u, i])
                # load_checkpoint arms the directory for saving too, and
                # the continuation fit below writes new snapshots — each
                # topology restores from its own copy so every restore
                # sees the ORIGINAL 2-way snapshot
                work = tmp_path / f"restore_{len(shape)}x{shape[-1]}"
                shutil.copytree(tmp_path / "orig", work)
                m2.estimator.load_checkpoint(str(work))
                got = _table_leaves(m2.estimator.params)
                for name, want in saved.items():
                    np.testing.assert_array_equal(got[name], want), name
                assert m2.evaluate([u, i], y, batch_size=16)["loss"] \
                    == pytest.approx(loss, rel=1e-6), axes
                # and training continues on the new topology
                m2.fit([u, i], y, batch_size=16, epochs=2, verbose=False)
        finally:
            init_zoo_context()

    def test_elastic_growth_restore(self, tmp_path):
        """Restore a 32-row-table snapshot into a model built with 64
        rows: snapshot rows bit-exact, new rows keep fresh init, and
        training continues (new rows' Adam moments start at zero)."""
        from analytics_zoo_tpu import init_zoo_context

        u, i, y = _pair_data(31, 47)
        try:
            init_zoo_context(mesh_shape=(4, 2),
                             axis_names=("data", "model"))
            m = _make_ncf(users=31)
            m.estimator.set_checkpoint(str(tmp_path))
            m.fit([u, i], y, batch_size=16, epochs=1, verbose=False)
            saved = _table_leaves(m.estimator.params)

            m2 = _make_ncf(users=63)          # 64 rows: vocab grew
            m2.estimator._ensure_built([u, i])
            fresh = _table_leaves(m2.estimator.params)
            m2.estimator.load_checkpoint(str(tmp_path))
            got = _table_leaves(m2.estimator.params)
            for name in ("mlp_user_embed", "mf_user_embed"):
                assert got[name].shape == (64, 20)
                np.testing.assert_array_equal(got[name][:32], saved[name])
                np.testing.assert_array_equal(got[name][32:],
                                              fresh[name][32:])
            # item tables did not grow: plain bit-exact restore
            np.testing.assert_array_equal(got["mlp_item_embed"],
                                          saved["mlp_item_embed"])
            u2, i2, y2 = _pair_data(63, 47, seed=1)
            m2.fit([u2, i2], y2, batch_size=16, epochs=2, verbose=False)
        finally:
            init_zoo_context()

    def test_shrinking_restore_is_an_error(self, tmp_path):
        from analytics_zoo_tpu import init_zoo_context

        u, i, y = _pair_data(63, 47)
        try:
            init_zoo_context(mesh_shape=(4, 2),
                             axis_names=("data", "model"))
            m = _make_ncf(users=63)
            m.estimator.set_checkpoint(str(tmp_path))
            m.fit([u, i], y, batch_size=16, epochs=1, verbose=False)

            m2 = _make_ncf(users=31)
            m2.estimator._ensure_built([u, i])
            with pytest.raises(ValueError, match="shrink"):
                m2.estimator.load_checkpoint(str(tmp_path))
        finally:
            init_zoo_context()

    def test_grow_helpers_reject_incompatible_shapes(self):
        from analytics_zoo_tpu.parallel import (grow_restored_opt_state,
                                                grow_restored_tree)

        restored = {"t": {"table": np.ones((8, 4), np.float32)}}
        built = {"t": {"table": np.zeros((16, 5), np.float32)}}
        with pytest.raises(ValueError, match="incompatible"):
            grow_restored_tree(restored, built, ("t",))
        with pytest.raises(ValueError, match="grow"):
            grow_restored_opt_state(
                {"m": np.ones((8, 4), np.float32)},
                {"m": np.zeros((8, 5), np.float32)})


# ---------------------------------------------------------------------------
# the lazy giant-table fixture + stream-cold-rows init
# ---------------------------------------------------------------------------


class TestSyntheticGiantTable:
    def test_header_only_accounting(self):
        from analytics_zoo_tpu.data import SyntheticGiantTable

        t = SyntheticGiantTable(10 ** 8, 64, seed=1)
        assert t.nbytes == 10 ** 8 * 64 * 4
        assert len(t) == 10 ** 8
        assert t.shape == (10 ** 8, 64)

    def test_rows_deterministic_and_range_independent(self):
        from analytics_zoo_tpu.data import SyntheticGiantTable

        t = SyntheticGiantTable(10 ** 8, 16, seed=7)
        a = t.rows(5_000_000, 5_000_004)
        b = t.rows(5_000_002, 5_000_010)
        np.testing.assert_array_equal(a[2:], b[:2])
        np.testing.assert_array_equal(
            t.row(99_999_999), t.rows(99_999_998, 10 ** 8)[1])
        # same (seed, row) on a fresh instance: identical values
        np.testing.assert_array_equal(
            SyntheticGiantTable(10 ** 8, 16, seed=7).rows(
                5_000_000, 5_000_004), a)
        assert not np.array_equal(
            SyntheticGiantTable(10 ** 8, 16, seed=8).rows(
                5_000_000, 5_000_004), a)

    def test_chunked_generation_matches_unchunked(self):
        from analytics_zoo_tpu.data import SyntheticGiantTable

        t = SyntheticGiantTable(4096, 16, seed=3)
        whole = t.rows(0, 4096)
        t._CHUNK_CELLS = 1000          # force many ragged chunks
        np.testing.assert_array_equal(t.rows(0, 4096), whole)

    def test_values_bounded_and_centered(self):
        from analytics_zoo_tpu.data import SyntheticGiantTable

        t = SyntheticGiantTable(1 << 16, 8, seed=0, scale=0.05)
        block = t.rows(0, 1 << 16)
        assert np.all(np.abs(block) <= 0.05)
        assert abs(float(block.mean())) < 1e-3

    def test_bad_ranges_rejected(self):
        from analytics_zoo_tpu.data import SyntheticGiantTable

        t = SyntheticGiantTable(16, 4)
        with pytest.raises(IndexError):
            t.rows(0, 17)
        with pytest.raises(ValueError):
            SyntheticGiantTable(0, 4)

    def test_init_table_sharded_streams_each_shard(self, tp_ctx):
        from jax.sharding import PartitionSpec as P

        from analytics_zoo_tpu.data import SyntheticGiantTable
        from analytics_zoo_tpu.parallel import init_table_sharded

        src = SyntheticGiantTable(60, 8, seed=3)
        arr = init_table_sharded(tp_ctx.mesh, 60, 8, src, axis="model")
        assert arr.shape == (64, 8)            # ROW_ALIGN padding
        assert arr.sharding.spec == P("model", None)
        assert arr.addressable_shards[0].data.shape == (32, 8)
        host = np.asarray(arr)
        np.testing.assert_array_equal(host[:60], src.rows(0, 60))
        assert np.all(host[60:] == 0)          # padding tail


# ---------------------------------------------------------------------------
# within-batch dedup through the sharded lookup (ISSUE 19 tentpole a)
# ---------------------------------------------------------------------------


class TestShardedDedup:
    @pytest.mark.parametrize("combiner", ["sum", "mean", "sqrtn"])
    def test_dedup_matches_naive(self, tp_ctx, combiner):
        import jax.numpy as jnp

        from analytics_zoo_tpu.parallel import sharded_bag

        rs = np.random.RandomState(10)
        table = jnp.asarray(rs.randn(48, 8).astype(np.float32))
        ids = jnp.asarray(rs.randint(0, 48, (16, 5)).astype(np.int32))
        ids = ids.at[0, :3].set(0)
        naive = np.asarray(sharded_bag(table, ids, combiner, pad_id=0,
                                       mesh=tp_ctx.mesh, axis="model",
                                       dedup=False))
        got = np.asarray(sharded_bag(table, ids, combiner, pad_id=0,
                                     mesh=tp_ctx.mesh, axis="model",
                                     dedup=True))
        np.testing.assert_allclose(got, naive, rtol=1e-6, atol=1e-7)

    def test_gather_through_dedup_matches_take(self, tp_ctx):
        import jax.numpy as jnp

        from analytics_zoo_tpu.parallel import sharded_gather

        rs = np.random.RandomState(11)
        table = jnp.asarray(rs.randn(48, 6).astype(np.float32))
        ids = jnp.asarray(rs.randint(0, 48, (8, 3)).astype(np.int32))
        got = np.asarray(sharded_gather(table, ids, mesh=tp_ctx.mesh,
                                        axis="model", dedup=True))
        np.testing.assert_allclose(
            got, np.asarray(jnp.take(table, ids, axis=0)),
            rtol=1e-6, atol=1e-7)

    def test_gradient_matches_naive(self, tp_ctx):
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.parallel import sharded_bag

        rs = np.random.RandomState(12)
        table = jnp.asarray(rs.randn(48, 8).astype(np.float32))
        ids = jnp.asarray(rs.randint(0, 48, (16, 4)).astype(np.int32))

        def loss(dedup):
            return lambda t: jnp.sum(sharded_bag(
                t, ids, "sum", pad_id=0, mesh=tp_ctx.mesh,
                axis="model", dedup=dedup) ** 2)

        g_d = np.asarray(jax.grad(loss(True))(table))
        g_n = np.asarray(jax.grad(loss(False))(table))
        np.testing.assert_allclose(g_d, g_n, rtol=1e-6, atol=1e-6)

    def test_fully_duplicated_batch_regression(self, tp_ctx):
        """EVERY slot the same id: unique collapses to one live row —
        the forward and per-occurrence gradient must survive both the
        inverse-index scatter and the psum exchange."""
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag
        from analytics_zoo_tpu.parallel import sharded_bag

        rs = np.random.RandomState(13)
        table = jnp.asarray(rs.randn(48, 8).astype(np.float32))
        ids = jnp.full((16, 4), 37, jnp.int32)
        ref = np.asarray(embedding_bag(table, ids, "sum", pad_id=None))
        got = np.asarray(sharded_bag(table, ids, "sum", pad_id=None,
                                     mesh=tp_ctx.mesh, axis="model",
                                     dedup=True))
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)
        g = np.asarray(jax.grad(lambda t: jnp.sum(sharded_bag(
            t, ids, "sum", pad_id=None, mesh=tp_ctx.mesh,
            axis="model", dedup=True)))(table))
        np.testing.assert_allclose(g[37], np.full(8, 64.0, np.float32),
                                   rtol=1e-6)
        assert float(np.abs(np.delete(g, 37, axis=0)).max()) == 0.0

    def test_all_pad_bag_regression(self, tp_ctx):
        import jax.numpy as jnp

        from analytics_zoo_tpu.ops.embedding_bag import embedding_bag
        from analytics_zoo_tpu.parallel import sharded_bag

        rs = np.random.RandomState(14)
        table = jnp.asarray(rs.randn(48, 8).astype(np.float32))
        ids = jnp.asarray(rs.randint(1, 48, (8, 4)).astype(np.int32))
        ids = ids.at[3].set(0)                # one fully-padded bag
        got = np.asarray(sharded_bag(table, ids, "mean", pad_id=0,
                                     mesh=tp_ctx.mesh, axis="model",
                                     dedup=True))
        ref = np.asarray(embedding_bag(table, ids, "mean", pad_id=0))
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)
        np.testing.assert_array_equal(got[3], np.zeros(8, np.float32))


# ---------------------------------------------------------------------------
# the hot-row replication cache (ISSUE 19 tentpole b)
# ---------------------------------------------------------------------------


class TestHotRowCache:
    def _cache(self, table_np, capacity=4, period=30.0, clock=None):
        from analytics_zoo_tpu.parallel import HotRowCache

        kw = {} if clock is None else {"clock": clock}
        return HotRowCache("t/test", capacity, dim=table_np.shape[1],
                           refresh_period_s=period, **kw)

    def test_cold_bucket_is_bounded_powers_of_two(self):
        from analytics_zoo_tpu.parallel import cold_bucket
        from analytics_zoo_tpu.parallel.hot_cache import MIN_COLD_BUCKET

        assert MIN_COLD_BUCKET == 8
        assert cold_bucket(0) == 8
        assert cold_bucket(1) == 8
        assert cold_bucket(8) == 8
        assert cold_bucket(9) == 16
        assert cold_bucket(129) == 256

    def test_frequency_ranking_deterministic_under_ties(self):
        table = np.zeros((16, 4), np.float32)
        c = self._cache(table, capacity=3)
        c.record([5, 5, 5, 9, 9, 2, 7])       # tie between 2 and 7
        np.testing.assert_array_equal(c.top_ids(), [5, 9, 2])
        c.record(np.asarray([[7, 7]]))        # any shape folds in
        # 7 ties 5 at count 3 -> ascending id breaks it: 5 stays first
        np.testing.assert_array_equal(c.top_ids(), [5, 7, 9])

    def test_route_and_metrics(self):
        from analytics_zoo_tpu.observe.metrics import METRICS

        table = np.arange(32, dtype=np.float32).reshape(8, 4)
        c = self._cache(table, capacity=2)
        c.record([1, 1, 6])
        c.refresh(lambda ids: table[np.asarray(ids, np.int64)])
        before = METRICS.snapshot()
        slots, hot = c.route([1, 3, 6, 1])
        np.testing.assert_array_equal(hot, [True, False, True, True])
        np.testing.assert_array_equal(c.take(slots[hot]),
                                      table[[1, 6, 1]])
        snap = METRICS.snapshot()
        hit_key = ("table_hot_cache_lookups_total",
                   (("outcome", "hit"), ("table", "t/test")))
        miss_key = ("table_hot_cache_lookups_total",
                    (("outcome", "miss"), ("table", "t/test")))
        bytes_key = ("table_hot_cache_bytes_saved_total",
                     (("table", "t/test"),))
        assert snap.counters[hit_key] == \
            before.counters.get(hit_key, 0) + 3
        assert snap.counters[miss_key] == \
            before.counters.get(miss_key, 0) + 1
        assert snap.counters[bytes_key] == \
            before.counters.get(bytes_key, 0) + 3 * 4 * 4
        assert c.stats()["hit_rate"] == pytest.approx(0.75)

    def test_staleness_bounded_by_refresh_period(self):
        """The acceptance bound: a cached row can lag the authoritative
        table by at most ``refresh_period_s`` on the injected clock —
        stale reads before the period, fresh right after it."""
        now = [100.0]
        table = np.ones((8, 4), np.float32)
        c = self._cache(table, capacity=2, period=10.0,
                        clock=lambda: now[0])
        c.record([0, 0, 3])
        reads = {"n": 0}

        def reader(ids):
            reads["n"] += 1
            return table[np.asarray(ids, np.int64)]

        assert c.maybe_refresh(reader)        # never refreshed: fires
        v1 = c.version
        table += 1.0                          # the optimizer moved
        now[0] = 109.9                        # inside the period
        assert not c.maybe_refresh(reader)
        np.testing.assert_array_equal(c.take([0]),
                                      np.ones((1, 4), np.float32))
        now[0] = 110.1                        # period elapsed
        assert c.maybe_refresh(reader)
        assert c.version == v1 + 1 and reads["n"] == 2
        np.testing.assert_array_equal(
            c.take([0]), np.full((1, 4), 2.0, np.float32))
        assert c.stats()["last_refresh"] == 110.1

    def test_invalidate_drops_replica_keeps_traffic_knowledge(self):
        from analytics_zoo_tpu.observe.metrics import METRICS

        table = np.ones((8, 4), np.float32)
        c = self._cache(table, capacity=2)
        c.record([2, 2, 5])
        c.refresh(lambda ids: table[np.asarray(ids, np.int64)])
        assert c.stats()["cached_rows"] == 2
        before = METRICS.snapshot()
        c.invalidate("swap")
        key = ("table_hot_cache_refresh_total",
               (("event", "invalidate_swap"), ("table", "t/test")))
        assert METRICS.snapshot().counters[key] == \
            before.counters.get(key, 0) + 1
        _, hot = c.route([2, 5])              # every id misses now
        assert not hot.any()
        assert c.stats()["cached_rows"] == 0
        # frequency knowledge survives: the next refresh re-ranks from
        # the SAME counts and repopulates immediately
        c.refresh(lambda ids: table[np.asarray(ids, np.int64)])
        assert c.stats()["cached_rows"] == 2
        _, hot = c.route([2, 5])
        assert hot.all()

    def test_snapshot_pins_route_take_across_refresh(self):
        """The route/take atomicity contract: both calls against ONE
        snapshot stay consistent even when a refresh re-ranks (or an
        invalidate empties) the replica between them — the race a
        supervisor refresh landing mid-lookup would otherwise hit."""
        table = np.arange(64, dtype=np.float32).reshape(16, 4)
        c = self._cache(table, capacity=2)
        c.record([3, 3, 9])
        c.refresh(lambda ids: table[np.asarray(ids, np.int64)])
        snap = c.snapshot()
        slots, hot = c.route([3, 9], snapshot=snap)
        assert hot.all()
        # a refresh with a DIFFERENT ranking lands mid-lookup...
        c.record([11] * 10 + [14] * 9)
        c.refresh(lambda ids: table[np.asarray(ids, np.int64)])
        np.testing.assert_array_equal(
            c.snapshot().sorted_ids, [11, 14])   # replica re-ranked
        # ...but the pinned snapshot still serves the routed ids' rows
        np.testing.assert_array_equal(c.take(slots, snapshot=snap),
                                      table[[3, 9]])
        # even a full invalidate can't break the pinned pair
        c.invalidate("swap")
        np.testing.assert_array_equal(c.take(slots, snapshot=snap),
                                      table[[3, 9]])
        # an UN-pinned take against the emptied replica is exactly the
        # hazard the snapshot exists to avoid
        with pytest.raises(IndexError):
            c.take(slots)

    def test_tracked_ids_bounded_heavy_hitters_survive(self):
        """The frequency tracker never exceeds ``max_tracked_ids`` no
        matter how wide the id stream — and the lossy-counting decay
        keeps the heavy hitters ranked on top."""
        from analytics_zoo_tpu.parallel import HotRowCache

        c = HotRowCache("t/bound", 2, dim=4, max_tracked_ids=8)
        c.record([5] * 50 + [7] * 40)         # the heavy hitters
        for start in range(100, 160, 20):     # wide singleton tail
            c.record(np.arange(start, start + 20))
        s = c.stats()
        assert s["max_tracked_ids"] == 8
        assert s["tracked_ids"] <= 8
        np.testing.assert_array_equal(c.top_ids(), [5, 7])
        # default bound scales with capacity, floored
        d = HotRowCache("t/dflt", 1024, dim=4)
        assert d.max_tracked_ids == 32 * 1024
        with pytest.raises(ValueError, match="max_tracked_ids"):
            HotRowCache("t/bad", 16, dim=4, max_tracked_ids=4)

    def test_bad_inputs_rejected(self):
        from analytics_zoo_tpu.parallel import HotRowCache

        with pytest.raises(ValueError, match="capacity"):
            HotRowCache("t", 0, dim=4)
        c = self._cache(np.zeros((4, 4), np.float32))
        c.record([1])
        with pytest.raises(ValueError, match="row_reader"):
            c.refresh(lambda ids: np.zeros((len(ids), 7)))


# ---------------------------------------------------------------------------
# two-tier cached lookups on the mesh (transfer-guarded parity suite)
# ---------------------------------------------------------------------------


def _warm_cache(table, mesh, capacity=16, ids=None):
    from analytics_zoo_tpu.parallel import HotRowCache, table_row_reader

    c = HotRowCache("t/parity", capacity, dim=int(table.shape[1]),
                    mesh=mesh)
    c.record(ids if ids is not None else np.arange(capacity))
    c.refresh(table_row_reader(table))
    return c


class TestCachedShardedLookup:
    @pytest.mark.transfer_guard
    def test_cached_gather_matches_uncached(self, tp_ctx):
        """The acceptance gate: cached-vs-uncached parity at rtol 1e-6
        on zipfian traffic, with the serving-side path running under
        ``transfer_guard("disallow")`` — its cold fetch and replica
        reads are EXPLICIT staging chokepoints, never implicit."""
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.data.zipf import zipfian_ids
        from analytics_zoo_tpu.parallel import cached_sharded_gather
        from analytics_zoo_tpu.parallel import sharded_gather

        rs = np.random.RandomState(20)
        table = jnp.asarray(rs.randn(64, 8).astype(np.float32))
        cache = _warm_cache(table, tp_ctx.mesh,
                            ids=zipfian_ids(64, 2048, 1.0, seed=0))
        meas = zipfian_ids(64, 256, 1.0, seed=1).reshape(16, 16)
        with jax.transfer_guard("allow"):
            want = np.asarray(jax.device_get(sharded_gather(
                table, jnp.asarray(meas), mesh=tp_ctx.mesh,
                axis="model")))
        with jax.transfer_guard("disallow"):
            got = cached_sharded_gather(cache, table, meas,
                                        mesh=tp_ctx.mesh, axis="model")
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
        assert cache.stats()["hits"] > 0      # the hot tier really hit

    @pytest.mark.transfer_guard
    def test_cached_bag_matches_uncached(self, tp_ctx):
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.parallel import (cached_sharded_bag,
                                                sharded_bag)

        rs = np.random.RandomState(21)
        table = jnp.asarray(rs.randn(48, 8).astype(np.float32))
        cache = _warm_cache(table, tp_ctx.mesh)
        ids = rs.randint(0, 48, (16, 5)).astype(np.int32)
        ids[0, :3] = 0                        # pad slots
        for combiner, pad in (("mean", 0), ("sum", None), ("sqrtn", 0)):
            with jax.transfer_guard("allow"):
                want = np.asarray(jax.device_get(sharded_bag(
                    table, jnp.asarray(ids), combiner, pad_id=pad,
                    mesh=tp_ctx.mesh, axis="model")))
            with jax.transfer_guard("disallow"):
                got = cached_sharded_bag(cache, table, ids, combiner,
                                         pad_id=pad, mesh=tp_ctx.mesh,
                                         axis="model")
            # atol 1e-6: the host-side bag reduces in a different f32
            # association order than the on-device lowering
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                       err_msg=combiner)

    @pytest.mark.transfer_guard
    def test_fully_hot_batch_skips_the_exchange(self, tp_ctx):
        """Every id cached -> the cold sharded program never runs: the
        lookup completes under the guard with zero device dispatches
        beyond the replica read, and every lookup counts as a hit."""
        import jax
        import jax.numpy as jnp

        rs = np.random.RandomState(22)
        table = jnp.asarray(rs.randn(32, 4).astype(np.float32))
        cache = _warm_cache(table, tp_ctx.mesh, capacity=8)
        ids = np.asarray([[0, 7], [3, 3]], np.int64)
        with jax.transfer_guard("disallow"):
            from analytics_zoo_tpu.parallel import cached_sharded_gather

            got = cached_sharded_gather(cache, table, ids,
                                        mesh=tp_ctx.mesh, axis="model")
        np.testing.assert_allclose(
            got, np.asarray(table)[ids], rtol=1e-6, atol=1e-7)
        s = cache.stats()
        assert s["hits"] == s["lookups"] == 4

    def test_post_invalidate_parity_through_cold_path(self, tp_ctx):
        import jax.numpy as jnp

        from analytics_zoo_tpu.parallel import cached_sharded_gather

        rs = np.random.RandomState(23)
        table = jnp.asarray(rs.randn(48, 8).astype(np.float32))
        cache = _warm_cache(table, tp_ctx.mesh)
        cache.invalidate("swap")
        ids = rs.randint(0, 48, (8, 3))
        got = cached_sharded_gather(cache, table, ids,
                                    mesh=tp_ctx.mesh, axis="model")
        np.testing.assert_allclose(got, np.asarray(table)[ids],
                                   rtol=1e-6, atol=1e-7)
        assert cache.stats()["hits"] == 0     # all-cold, still exact

    def test_refresh_after_weight_change_serves_new_rows(self, tp_ctx):
        """Staleness contract end to end: a table update is invisible
        until the next refresh, exact immediately after it."""
        import jax.numpy as jnp

        from analytics_zoo_tpu.parallel import (cached_sharded_gather,
                                                table_row_reader)

        rs = np.random.RandomState(24)
        table = jnp.asarray(rs.randn(32, 4).astype(np.float32))
        cache = _warm_cache(table, tp_ctx.mesh, capacity=8)
        new_table = table + 1.0
        ids = np.asarray([[0, 5, 7]])         # all hot -> all stale
        got = cached_sharded_gather(cache, new_table, ids,
                                    mesh=tp_ctx.mesh, axis="model")
        np.testing.assert_allclose(got, np.asarray(table)[ids],
                                   rtol=1e-6, atol=1e-7)
        cache.refresh(table_row_reader(new_table))
        got = cached_sharded_gather(cache, new_table, ids,
                                    mesh=tp_ctx.mesh, axis="model")
        np.testing.assert_allclose(got, np.asarray(new_table)[ids],
                                   rtol=1e-6, atol=1e-7)

    @pytest.mark.transfer_guard
    def test_pad_slots_skip_route_metrics_and_cold(self, tp_ctx):
        """Pad slots never enter the routing tier: they count in NO
        lookup metric (the hit-rate gauge stays pure
        traffic) and an all-pad bag triggers NO cold exchange at all —
        it completes under the transfer guard on an EMPTY cache."""
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.observe.metrics import METRICS
        from analytics_zoo_tpu.parallel import (HotRowCache,
                                                cached_sharded_bag)

        rs = np.random.RandomState(25)
        table = jnp.asarray(rs.randn(32, 4).astype(np.float32))
        cache = HotRowCache("t/pads", 8, dim=4, mesh=tp_ctx.mesh)
        before = METRICS.snapshot().counters
        ids = np.zeros((3, 5), np.int64)      # every slot is the pad
        with jax.transfer_guard("disallow"):  # no cold fetch allowed
            got = cached_sharded_bag(cache, table, ids, "mean",
                                     pad_id=0, mesh=tp_ctx.mesh,
                                     axis="model")
        np.testing.assert_array_equal(got, np.zeros((3, 4), np.float32))
        after = METRICS.snapshot().counters
        for outcome in ("hit", "miss"):
            key = ("table_hot_cache_lookups_total",
                   (("outcome", outcome), ("table", "t/pads")))
            assert after.get(key, 0) == before.get(key, 0)
        assert cache.stats()["lookups"] == 0
        # a mixed bag routes (and counts) ONLY its valid slots
        warm = _warm_cache(table, tp_ctx.mesh, capacity=8)
        mixed = np.asarray([[3, 5, 0, 0, 0]], np.int64)
        with jax.transfer_guard("disallow"):  # both valid ids are hot
            cached_sharded_bag(warm, table, mixed, "sum", pad_id=0,
                               mesh=tp_ctx.mesh, axis="model")
        assert warm.stats()["lookups"] == 2
        assert warm.stats()["hits"] == 2

    def test_layer_cached_forward_matches_forward(self, tp_ctx):
        import jax

        from analytics_zoo_tpu.nn.layers import ShardedEmbeddingTable
        from analytics_zoo_tpu.parallel import (HotRowCache,
                                                TableShardedStrategy,
                                                table_row_reader)

        lyr = ShardedEmbeddingTable(48, 8, combiner="mean", name="t")
        p = lyr.build_params(jax.random.PRNGKey(0), (4, 3))
        cache = HotRowCache("t", 16, dim=8, mesh=tp_ctx.mesh)
        cache.record(np.arange(16))
        cache.refresh(table_row_reader(p["table"]))
        ids = np.asarray(
            np.random.RandomState(0).randint(0, 48, (8, 3)), np.int32)
        strat = TableShardedStrategy(tables=("t",))
        with strat.activate(tp_ctx.mesh):
            want = np.asarray(lyr.forward(p, ids))
        got = lyr.cached_forward(p, ids, cache, axis="model")
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# serving cache lifecycle (fast, in-process)
# ---------------------------------------------------------------------------


class TestServingHotCacheLifecycle:
    def test_record_refresh_invalidate_through_serving(self):
        """The whole serving lifecycle in one fast pod: the pipeline
        builds one cache per sharded table, dispatch id streams fill
        its frequency counts, the supervisor's ``hot_cache_refresh``
        check populates the replica on the configured period, and a
        ``swap_replicas`` hot reload invalidates it (then the next
        supervisor pass rebuilds from the still-valid counts)."""
        import time as _time

        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.deploy import InferenceModel
        from analytics_zoo_tpu.deploy.serving import (ClusterServing,
                                                      InputQueue,
                                                      MemoryQueue,
                                                      OutputQueue,
                                                      ServingConfig)
        from analytics_zoo_tpu.nn import Input, Model
        from analytics_zoo_tpu.nn.layers.core import Dense
        from analytics_zoo_tpu.nn.layers.sharded_embedding import \
            ShardedEmbeddingTable

        try:
            # refresh period 0: every supervisor pass refreshes, so the
            # test needs no sleeps beyond the supervisor cadence
            init_zoo_context(mesh_shape=(4, 2),
                             axis_names=("data", "model"),
                             table_hot_cache_capacity=16,
                             table_hot_cache_refresh_s=0.0)
            from analytics_zoo_tpu.core.context import get_zoo_context

            mesh = get_zoo_context().mesh
            ids_in = Input(shape=(4,), dtype=jnp.int32, name="ids")
            bag = ShardedEmbeddingTable(64, 8, combiner="mean",
                                        name="embed")(ids_in)
            net = Model([ids_in], Dense(4, name="head")(bag),
                        name="bagnet")
            net._sharded_tables = ("embed",)
            net.compile(optimizer="adam", loss="mse")
            est = net.estimator
            params, state = jax.jit(
                lambda r: est.model.init(r, (2, 4)))(jax.random.PRNGKey(0))
            m = InferenceModel.from_keras_net(net, params, state,
                                              batch_buckets=(1, 4))
            srv = ClusterServing(
                m, MemoryQueue(),
                ServingConfig(batch_size=4, replicas=1, mesh_replicas=1,
                              supervisor_interval_s=0.05),
                mesh=mesh).start()
            try:
                stats = srv.hot_cache_stats()
                assert list(stats) == ["default/embed"]
                assert stats["default/embed"]["capacity"] == 16

                inq, outq = InputQueue(srv.queue), OutputQueue(srv.queue)
                x = np.random.RandomState(0).randint(
                    0, 64, (8, 4)).astype(np.int32)
                rids = [inq.enqueue(ids=x[i]) for i in range(len(x))]
                outs = [outq.query(r, timeout=60.0) for r in rids]
                assert not any(isinstance(o, dict) and "error" in o
                               for o in outs)

                # dispatch recorded the id streams; the supervisor's
                # refresh check populates the replica from them
                deadline = _time.monotonic() + 10.0
                while _time.monotonic() < deadline:
                    s = srv.hot_cache_stats()["default/embed"]
                    if s["cached_rows"] > 0:
                        break
                    _time.sleep(0.05)
                assert s["tracked_ids"] > 0
                assert 0 < s["cached_rows"] <= 16
                v_before = s["version"]

                # hot reload: the swap listener invalidates instantly…
                srv._executor.swap_replicas(srv._build_replicas())
                assert srv.hot_cache_stats()["default/embed"]["version"] \
                    > v_before
                # …and the next supervisor pass repopulates from the
                # surviving frequency counts
                deadline = _time.monotonic() + 10.0
                while _time.monotonic() < deadline:
                    s = srv.hot_cache_stats()["default/embed"]
                    if s["cached_rows"] > 0:
                        break
                    _time.sleep(0.05)
                assert s["cached_rows"] > 0
            finally:
                srv.stop()
        finally:
            init_zoo_context()

    def test_knob_off_builds_no_caches(self, zoo_ctx):
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu import init_zoo_context
        from analytics_zoo_tpu.deploy import InferenceModel
        from analytics_zoo_tpu.nn import Input, Model
        from analytics_zoo_tpu.nn.layers.core import Dense
        from analytics_zoo_tpu.nn.layers.sharded_embedding import \
            ShardedEmbeddingTable

        ids_in = Input(shape=(4,), dtype=jnp.int32, name="ids")
        bag = ShardedEmbeddingTable(64, 8, combiner="mean",
                                    name="embed")(ids_in)
        net = Model([ids_in], Dense(4, name="head")(bag), name="bagnet")
        net._sharded_tables = ("embed",)
        net.compile(optimizer="adam", loss="mse")
        params, state = net.estimator.model.init(
            jax.random.PRNGKey(0), (2, 4))
        m = InferenceModel.from_keras_net(net, params, state)
        try:
            init_zoo_context(table_hot_cache="off")
            assert m.enable_hot_caches() == {}
            assert m.hot_caches() == {}
        finally:
            init_zoo_context()
        assert m.enable_hot_caches(capacity=4)  # default auto builds
        m.record_hot_ids([np.asarray([1, 2, 2], np.int32),
                          np.zeros((2, 2), np.float32)])  # floats skip
        assert m.hot_caches()["embed"].stats()["tracked_ids"] == 2

    def test_record_hot_ids_routes_per_table(self, zoo_ctx):
        """Each table's cache records ONLY its own id streams: the
        graph-ancestor trace maps input fields to tables, so a
        multi-table model never cross-pollutes rankings and an integer
        non-id input (lengths here) never enters any cache."""
        import jax
        import jax.numpy as jnp

        from analytics_zoo_tpu.deploy import InferenceModel
        from analytics_zoo_tpu.nn import Input, Model
        from analytics_zoo_tpu.nn.layers.core import Dense
        from analytics_zoo_tpu.nn.layers.merge import merge
        from analytics_zoo_tpu.nn.layers.sharded_embedding import \
            ShardedEmbeddingTable

        u_in = Input(shape=(2,), dtype=jnp.int32, name="user")
        i_in = Input(shape=(2,), dtype=jnp.int32, name="item")
        l_in = Input(shape=(1,), dtype=jnp.int32, name="lengths")
        ue = ShardedEmbeddingTable(32, 4, combiner="mean",
                                   name="u_embed")(u_in)
        ie = ShardedEmbeddingTable(32, 4, combiner="mean",
                                   name="i_embed")(i_in)
        head = Dense(2, name="head")(merge([ue, ie], mode="concat"))
        net = Model([u_in, i_in, l_in], head, name="two_tables")
        net._sharded_tables = ("u_embed", "i_embed")
        assert net.input_ancestors("u_embed") == ("user",)
        assert net.input_ancestors("i_embed") == ("item",)
        net.compile(optimizer="adam", loss="mse")
        params, state = net.estimator.model.init(
            jax.random.PRNGKey(0), (2, 2), (2, 2), (2, 1))
        m = InferenceModel.from_keras_net(net, params, state)
        m.enable_hot_caches(capacity=4)
        m.record_hot_ids([np.asarray([1, 2, 2], np.int32),   # user
                          np.asarray([9, 9, 10], np.int32),  # item
                          np.asarray([7, 7, 7], np.int32)])  # lengths
        u, i = m.hot_caches()["u_embed"], m.hot_caches()["i_embed"]
        np.testing.assert_array_equal(np.sort(u.top_ids()), [1, 2])
        np.testing.assert_array_equal(np.sort(i.top_ids()), [9, 10])
        # explicit id_fields override beats the trace
        m.enable_hot_caches(capacity=4,
                            id_fields={"u_embed": ("item",)})
        m.record_hot_ids([np.asarray([1, 1], np.int32),
                          np.asarray([5, 6], np.int32),
                          np.asarray([8], np.int32)])
        np.testing.assert_array_equal(
            np.sort(m.hot_caches()["u_embed"].top_ids()), [5, 6])
