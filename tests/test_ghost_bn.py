"""Ghost-BN (stats_fraction) semantics + accuracy evidence.

BatchNorm's and the elementwise fusions bound ResNet-50's step on the
chip (PERF.md section 5).  ``BatchNormalization(stats_fraction=f)``
reads only the leading ``ceil(f*B)`` rows for training statistics — the
ghost-BN numerics (Hoffer et al. 2017); what it is worth in time is not
measured (the benchmark's cell runs full BN).
"""

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def fresh_names():
    from analytics_zoo_tpu.nn import reset_name_scope

    reset_name_scope()


def test_stats_slice_semantics(zoo_ctx):
    """Training stats come from the slice; normalization covers all rows;
    eval path ignores the knob entirely."""
    import jax

    from analytics_zoo_tpu.nn.layers.normalization import BatchNormalization

    rs = np.random.RandomState(0)
    x = rs.randn(8, 4, 4, 3).astype(np.float32)
    x[4:] += 10.0                        # tail rows shift the full stats
    bn = BatchNormalization(stats_fraction=0.5, epsilon=1e-3)
    params, state = bn.init(jax.random.PRNGKey(0), x.shape)
    y, new_state = bn.call(params, state, x, training=True)
    mean_half = x[:4].mean(axis=(0, 1, 2))
    var_half = x[:4].var(axis=(0, 1, 2))
    expect = (x - mean_half) / np.sqrt(var_half + 1e-3)
    np.testing.assert_allclose(np.asarray(y), expect, atol=1e-4)
    # moving stats track the slice stats
    np.testing.assert_allclose(
        np.asarray(new_state["moving_mean"]), 0.01 * mean_half, atol=1e-5)
    # eval: moving stats only, knob inert
    y_eval, st2 = bn.call(params, new_state, x, training=False)
    assert st2 is new_state


def test_invalid_fraction_rejected(zoo_ctx):
    from analytics_zoo_tpu.nn.layers.normalization import BatchNormalization

    with pytest.raises(ValueError, match="stats_fraction"):
        BatchNormalization(stats_fraction=0.0)
    with pytest.raises(ValueError, match="stats_fraction"):
        BatchNormalization(stats_fraction=1.5)


def test_ghost_bn_convergence_parity(zoo_ctx):
    """Accuracy check: a conv+BN classifier on the texture task reaches
    the same validation accuracy with quarter-batch stats."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.nn.layers import (Activation, BatchNormalization,
                                             Convolution2D, Dense, Flatten,
                                             MaxPooling2D)
    from analytics_zoo_tpu.nn.topology import Sequential

    init_zoo_context()
    rs = np.random.RandomState(0)
    n, size = 512, 16
    y = rs.randint(0, 2, n).astype(np.int32)
    x = rs.rand(n, size, size, 3).astype(np.float32) * 0.5
    checker = np.indices((8, 8)).sum(0) % 2
    for i in range(n):
        if y[i]:
            cx, cy = rs.randint(0, size - 8, 2)
            x[i, cy:cy + 8, cx:cx + 8, 0] += 0.5 * checker
    split = int(0.85 * n)

    def run(frac):
        reset_name_scope()
        m = Sequential()
        m.add(Convolution2D(8, 3, 3, border_mode="same", bias=False,
                            input_shape=(size, size, 3)))
        m.add(BatchNormalization(stats_fraction=frac))
        m.add(Activation("relu"))
        m.add(MaxPooling2D((2, 2)))
        m.add(Convolution2D(16, 3, 3, border_mode="same", bias=False))
        m.add(BatchNormalization(stats_fraction=frac))
        m.add(Activation("relu"))
        m.add(Flatten())
        m.add(Dense(2, activation="softmax"))
        m.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        m.fit(x[:split], y[:split], batch_size=64, nb_epoch=6,
              verbose=False)
        return m.evaluate(x[split:], y[split:],
                          batch_size=128)["accuracy"]

    acc_full = run(1.0)
    acc_ghost = run(0.25)
    assert acc_ghost > 0.8
    assert acc_ghost >= acc_full - 0.06   # parity within noise


def test_ghost_bn_eighth_fraction_parity(zoo_ctx):
    """stats_fraction=0.125 (ghost batch 32 at batch 256 — the standard
    large-batch ghost size) holds accuracy parity too."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.nn.layers import (Activation, BatchNormalization,
                                             Convolution2D, Dense, Flatten,
                                             MaxPooling2D)
    from analytics_zoo_tpu.nn.topology import Sequential

    init_zoo_context()
    rs = np.random.RandomState(1)
    n, size = 512, 16
    y = rs.randint(0, 2, n).astype(np.int32)
    x = rs.rand(n, size, size, 3).astype(np.float32) * 0.5
    checker = np.indices((8, 8)).sum(0) % 2
    for i in range(n):
        if y[i]:
            cx, cy = rs.randint(0, size - 8, 2)
            x[i, cy:cy + 8, cx:cx + 8, 0] += 0.5 * checker
    split = int(0.85 * n)

    def run(frac):
        reset_name_scope()
        m = Sequential()
        m.add(Convolution2D(8, 3, 3, border_mode="same", bias=False,
                            input_shape=(size, size, 3)))
        m.add(BatchNormalization(stats_fraction=frac))
        m.add(Activation("relu"))
        m.add(MaxPooling2D((2, 2)))
        m.add(Convolution2D(16, 3, 3, border_mode="same", bias=False))
        m.add(BatchNormalization(stats_fraction=frac))
        m.add(Activation("relu"))
        m.add(Flatten())
        m.add(Dense(2, activation="softmax"))
        m.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        m.fit(x[:split], y[:split], batch_size=256, nb_epoch=8,
              verbose=False)
        return m.evaluate(x[split:], y[split:],
                          batch_size=128)["accuracy"]

    acc_full = run(1.0)
    acc_ghost = run(0.125)       # ghost batch = 32 rows of the 256
    assert acc_ghost > 0.75
    assert acc_ghost >= acc_full - 0.08
