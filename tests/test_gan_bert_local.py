"""GANEstimator, BERT task estimators, LocalEstimator, TorchCriterion
(reference tfpark/gan/gan_estimator.py, tfpark/text/estimator/bert_*.py,
pipeline/estimator/LocalEstimator.scala, TorchCriterion.scala)."""

import numpy as np
import pytest

from analytics_zoo_tpu.tfpark import (BERTNER, BERTSQuAD, BERTClassifier,
                                      GANEstimator, TorchCriterion)
from analytics_zoo_tpu.train.local_estimator import LocalEstimator


def _mlp(out_dim, in_dim, activation=None):
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.nn.layers.core import Dense
    from analytics_zoo_tpu.nn.topology import Sequential

    reset_name_scope()
    m = Sequential()
    m.add(Dense(32, activation="relu", input_shape=(in_dim,)))
    m.add(Dense(out_dim, activation=activation))
    return m


class TestGANEstimator:
    def test_learns_a_gaussian(self, zoo_ctx):
        # 2D target distribution N([3, -1], 0.5I): after training the
        # generator's samples move toward the target mean.  The estimator
        # seeds itself from its context, and without one it takes the
        # GLOBAL context, which an earlier test in the same process may
        # have left at another seed (tests/test_featureset_streaming.py
        # does): the mean's error overshoots and swings (0.3 at its least,
        # 1.6-2.2 at epoch 16 on seeds 1, 2, 3), so the thresholds below
        # hold for the fixture's seed, not for whatever ran before.
        rs = np.random.RandomState(0)
        real = (rs.randn(2048, 2) * 0.5 + [3.0, -1.0]).astype(np.float32)
        gan = GANEstimator(generator=_mlp(2, 4),
                           discriminator=_mlp(1, 2), noise_dim=4,
                           ctx=zoo_ctx)
        gan.fit(real, batch_size=128, epochs=1, verbose=False)
        before = np.abs(gan.generate(512).mean(0) - [3.0, -1.0]).sum()
        gan.fit(real, batch_size=128, epochs=15, verbose=False)
        after = np.abs(gan.generate(512).mean(0) - [3.0, -1.0]).sum()
        assert after < before, (before, after)
        assert after < 1.5, after
        assert {"d_loss", "g_loss"} <= set(gan.history[-1])

    def test_alternation_counts(self, zoo_ctx):
        rs = np.random.RandomState(0)
        real = rs.randn(64, 2).astype(np.float32)
        gan = GANEstimator(generator=_mlp(2, 4),
                           discriminator=_mlp(1, 2), noise_dim=4,
                           discriminator_steps=2, generator_steps=1)
        gan.fit(real, batch_size=32, epochs=1, verbose=False)
        assert np.isfinite(gan.history[-1]["d_loss"])


class TestBERTEstimators:
    CFG = dict(vocab=100, hidden_size=32, n_block=1, nhead=2,
               intermediate_size=64, max_position_len=16)

    def _data(self, n=48, L=8, seed=0):
        rs = np.random.RandomState(seed)
        ids = rs.randint(1, 100, (n, L)).astype(np.int32)
        seg = np.zeros((n, L), np.int32)
        return ids, seg

    def test_classifier_trains(self, zoo_ctx):
        ids, seg = self._data()
        y = (ids[:, 0] > 50).astype(np.int32)
        clf = BERTClassifier(num_classes=2, bert_config=self.CFG)
        clf.compile(optimizer="adam",
                    loss="sparse_categorical_crossentropy_with_logits",
                    metrics=["accuracy"])
        clf.fit([ids, seg], y, batch_size=16, nb_epoch=2, verbose=False)
        preds = clf.predict([ids, seg], batch_size=16)
        assert preds.shape == (48, 2)

    def test_ner_shapes(self, zoo_ctx):
        ids, seg = self._data()
        tags = (ids % 5).astype(np.int32)                 # per-token labels
        ner = BERTNER(num_classes=5, bert_config=self.CFG)
        ner.compile(optimizer="adam",
                    loss="sparse_categorical_crossentropy_with_logits")
        ner.fit([ids, seg], tags, batch_size=16, nb_epoch=1, verbose=False)
        preds = ner.predict([ids, seg], batch_size=16)
        assert preds.shape == (48, 8, 5)

    def test_mask_is_honored(self, zoo_ctx):
        # with a padding mask, garbage in the padded region must not
        # change the (unpadded-token-derived) logits
        import jax

        ids, seg = self._data(4)
        mask = np.ones_like(ids, np.float32)
        mask[:, 5:] = 0.0
        clf = BERTClassifier(num_classes=2, bert_config=self.CFG)
        params, state = clf.init(jax.random.PRNGKey(0), ids.shape,
                                 seg.shape, ids.shape, mask.shape)
        out1, _ = clf.call(params, state, ids, seg, mask)
        ids2 = ids.copy()
        ids2[:, 5:] = 99                        # scramble padded tokens
        out2, _ = clf.call(params, state, ids2, seg, mask)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-4, atol=1e-5)

    def test_gan_zero_steps_rejected(self, zoo_ctx):
        with pytest.raises(ValueError, match=">= 1"):
            GANEstimator(generator=_mlp(2, 4), discriminator=_mlp(1, 2),
                         noise_dim=4, discriminator_steps=0)

    def test_squad_outputs_start_end(self, zoo_ctx):
        import jax

        ids, seg = self._data(8)
        qa = BERTSQuAD(bert_config=self.CFG)
        params, state = qa.init(jax.random.PRNGKey(0), ids.shape, seg.shape)
        (start, end), _ = qa.call(params, state, ids, seg)
        assert start.shape == (8, 8) and end.shape == (8, 8)


class TestLocalEstimator:
    def test_single_device_training(self):
        est = LocalEstimator(_mlp(1, 4), optimizer="adam", loss="mse")
        assert est.ctx.num_devices == 1
        rs = np.random.RandomState(0)
        x = rs.randn(128, 4).astype(np.float32)
        y = rs.randn(128, 1).astype(np.float32)
        hist = est.fit(x, y, batch_size=32, epochs=2, verbose=False)
        assert len(hist) == 2
        assert est.predict(x, batch_size=64).shape == (128, 1)


class TestTorchCriterion:
    def test_known_losses_map(self):
        torch = pytest.importorskip("torch")
        import jax.numpy as jnp

        crit = TorchCriterion(torch.nn.MSELoss())
        y = jnp.asarray([1.0, 2.0])
        p = jnp.asarray([1.5, 2.5])
        assert float(crit(y, p)) == pytest.approx(0.25)

        sl1 = TorchCriterion(torch.nn.SmoothL1Loss())
        val = float(sl1(jnp.asarray([0.0]), jnp.asarray([2.0])))
        ref = float(torch.nn.SmoothL1Loss()(torch.tensor([2.0]),
                                            torch.tensor([0.0])))
        assert val == pytest.approx(ref)

    def test_unknown_loss_raises(self):
        torch = pytest.importorskip("torch")
        from analytics_zoo_tpu.tfpark import UnsupportedLayerError

        class Weird(torch.nn.Module):
            pass

        with pytest.raises(UnsupportedLayerError, match="native mapping"):
            TorchCriterion(Weird())

    def test_usable_in_compile(self, zoo_ctx):
        torch = pytest.importorskip("torch")
        m = _mlp(1, 4)
        m.compile(optimizer="adam",
                  loss=TorchCriterion(torch.nn.MSELoss()))
        rs = np.random.RandomState(0)
        x = rs.randn(64, 4).astype(np.float32)
        y = rs.randn(64, 1).astype(np.float32)
        h = m.fit(x, y, batch_size=32, nb_epoch=2, verbose=False)
        assert h[-1]["loss"] < h[0]["loss"] * 2
