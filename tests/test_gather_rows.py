"""``data/gather.gather_rows``: the one batch gather of the host data
tier.  Whatever path it reads off its input, it returns
``np.asarray(a[idx])`` bit for bit, counts the path in
``data_gather_total{path}``, asks for the rows of one batch before any
row of the next, and hands a worker's exception to the caller; ``fit``
over an array-like trains as over the equal ``ndarray``."""

import os
import subprocess
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from analytics_zoo_tpu import native
from analytics_zoo_tpu.data import gather
from analytics_zoo_tpu.data.featureset import FeatureSet
from analytics_zoo_tpu.data.gather import gather_rows
from analytics_zoo_tpu.nn.layers.core import Dense
from analytics_zoo_tpu.nn.topology import Sequential
from analytics_zoo_tpu.observe.metrics import METRICS
from analytics_zoo_tpu.train.estimator import Estimator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIB = 1 << 20


@pytest.fixture(autouse=True)
def eight_threads(monkeypatch):
    """The pool as a host with 8 or more CPUs gets it, whatever this
    machine has."""
    pool = ThreadPoolExecutor(8)
    monkeypatch.setattr(gather, "_pool", (pool, 8))
    yield
    pool.shutdown()


class Rows:
    """Rows as ``fit`` takes them and no more: a shape, a dtype, fancy
    indexing.  Notes the index arrays it is asked for; ``bad`` is a row
    that cannot be read."""

    def __init__(self, a, bad=None):
        self.a, self.bad = a, bad
        self.shape, self.dtype, self.ndim = a.shape, a.dtype, a.ndim
        self.asked, self.threads = [], set()

    def __len__(self):
        return len(self.a)

    def __getitem__(self, idx):
        self.asked.append(np.array(idx))
        self.threads.add(threading.get_ident())
        if self.bad is not None and self.bad in idx:
            raise OSError(f"row {self.bad} cannot be read")
        return self.a[idx]


def _paths(snap):
    prefix = 'data_gather_total{path="'
    return {name[len(prefix):-2]: n
            for name, n in METRICS.delta(snap)["counters"].items()
            if name.startswith(prefix)}


def _source(kind, a, tmp_path):
    if kind == "ndarray":
        return a
    if kind == "memmap":
        m = np.lib.format.open_memmap(str(tmp_path / "a.npy"), mode="w+",
                                      dtype=a.dtype, shape=a.shape)
        m[...] = a
        return m
    return Rows(a)


# rows, the shape of one row, the length of the index: bytes asked for
SHAPES = {
    "labels-under": ((4096,), 256),                 # 1 KiB
    "labels-over": ((600_000,), 300_000),           # 1.14 MiB
    "rows-under": ((512, 8, 8, 3), 256),            # 192 KiB
    "rows-over": ((512, 32, 32, 3), 256),           # 3 MiB
    "rows-just-under": ((512, 1024), 255),          # 1 MiB less one row
    "rows-just-over": ((512, 1024), 256),           # 1 MiB
    "chunk-over": ((512, 4096), 4 * 32),            # K x B = 4 x 32: 2 MiB
    "odd-pieces-over": ((512, 4096), 67),           # 8 pieces of 9, last 4
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kind", ["ndarray", "memmap", "arraylike"])
def test_equals_the_fancy_index_and_counts_its_path(kind, shape, tmp_path):
    dims, n_idx = SHAPES[shape]
    rng = np.random.RandomState(len(shape))
    a = rng.randn(*dims).astype(np.float32)
    idx = rng.permutation(dims[0])[:n_idx]
    src = _source(kind, a, tmp_path)
    snap = METRICS.snapshot()
    got = gather_rows(src, idx)
    assert type(got) is np.ndarray and got.dtype == a.dtype
    assert got.shape == (n_idx,) + dims[1:]
    assert got.tobytes() == np.asarray(a[idx]).tobytes()
    if a[idx].nbytes < MIB:
        want = "inline"
    elif kind == "arraylike":
        want = "threads"
    else:
        want = "native" if native.available() else "threads"
    assert _paths(snap) == {want: 1}
    if kind == "arraylike" and want == "threads":
        # contiguous pieces of the index, each asked for once, on pool
        # threads
        assert 2 <= len(src.asked) <= 8
        assert sorted(np.concatenate(src.asked)) == sorted(idx)
        assert threading.get_ident() not in src.threads
    elif kind == "arraylike":
        assert len(src.asked) == 1
        assert src.threads == {threading.get_ident()}


def test_an_ndarray_without_the_native_library_takes_the_threads(monkeypatch):
    a = np.random.RandomState(0).randn(512, 4096).astype(np.float32)
    idx = np.random.RandomState(1).permutation(512)[:128]
    snap = METRICS.snapshot()
    with monkeypatch.context() as m:
        m.setattr(native, "available", lambda: False)
        assert gather_rows(a, idx).tobytes() == a[idx].tobytes()
    # not C-contiguous: the native memcpy does not apply either
    f = np.asfortranarray(a)
    assert gather_rows(f, idx).tobytes() == a[idx].tobytes()
    assert _paths(snap) == {"threads": 2}


@pytest.mark.parametrize("cpus,pieces", [(1, 1), (2, 1), (3, 1), (4, 4),
                                         (8, 8), (13, 8)])
def test_pieces_follow_the_cpus_the_process_may_use(monkeypatch, cpus,
                                                    pieces):
    """Two copies on two threads lose to one copy on one: under 4 CPUs
    no pool is made and a large batch is gathered inline."""
    monkeypatch.setattr(gather, "_pool", None)
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(cpus)))
    pool, n = gather._threads()
    try:
        assert n == pieces and (pool is None) == (pieces == 1)
        src = Rows(np.zeros((512, 4096), np.float32))
        snap = METRICS.snapshot()
        gather_rows(src, np.arange(512))
        assert _paths(snap) == {"threads" if pieces > 1 else "inline": 1}
        assert len(src.asked) == pieces
    finally:
        if pool is not None:
            pool.shutdown()


def test_a_forked_child_makes_a_pool_of_its_own():
    """The child inherits the executor and none of its threads."""
    code = """if True:
        import os, signal, time, numpy as np
        from analytics_zoo_tpu.data.gather import gather_rows

        class Slow:
            shape, dtype = (64, 1 << 16), np.dtype(np.float32)
            def __getitem__(self, idx):
                time.sleep(0.05)            # so that every thread starts
                return np.ones((len(idx), 1 << 16), np.float32)

        gather_rows(Slow(), np.arange(64))
        pid = os.fork()
        if pid == 0:
            signal.alarm(20)
            os._exit(0 if gather_rows(Slow(), np.arange(64)).all() else 1)
        print("child", os.waitpid(pid, 0)[1])
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               PYTHONWARNINGS="ignore")
    out = subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                         capture_output=True, text=True)
    assert out.stdout.strip().endswith("child 0"), out.stdout + out.stderr


def _estimator(width):
    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(width,)))
    m.add(Dense(1))
    return Estimator(m, loss="mse")


def _data(n, width):
    x = np.random.RandomState(0).randn(n, width).astype(np.float32)
    return x, x[:, :4].sum(axis=1, keepdims=True)


def test_fit_asks_for_a_batch_whole_before_the_next_and_no_row_twice(
        zoo_ctx, monkeypatch):
    """The benchmark cuts the rows asked for into batches in the order
    asked: pieces of one batch may come in any order, but never between
    those of another."""
    x, y = _data(8 * 64, 8192)                      # a batch: 2 MiB
    src = Rows(x)
    est = _estimator(8192)
    est.fit(src, y, batch_size=64, epochs=1, verbose=False)
    rows = np.concatenate(src.asked)
    assert len(src.asked) == 8 * 8 and len(rows) == len(x)
    assert len(np.unique(rows)) == len(x)
    # the batches of the same seed, each gathered in one piece
    ref = Rows(x)
    monkeypatch.setattr(gather, "_pool", (None, 1))
    _estimator(8192).fit(ref, y, batch_size=64, epochs=1, verbose=False)
    assert len(ref.asked) == 8
    for k, batch in enumerate(ref.asked):
        assert sorted(rows[k * 64:(k + 1) * 64]) == sorted(batch)


def test_fit_over_an_array_like_gives_the_ndarrays_losses(zoo_ctx):
    x, y = _data(4 * 64, 8192)
    snap = METRICS.snapshot()
    want = _estimator(8192).fit(x, y, batch_size=64, epochs=2,
                                verbose=False)
    ndarray_paths = _paths(snap)
    snap = METRICS.snapshot()
    got = _estimator(8192).fit(Rows(x), y, batch_size=64, epochs=2,
                               verbose=False)
    assert len(got) == 2
    assert [r["loss"] for r in got] == [r["loss"] for r in want]
    # 8 batches of x above the threshold and 8 of y under it
    assert _paths(snap) == {"threads": 8, "inline": 8}
    assert ndarray_paths == {
        "native" if native.available() else "threads": 8, "inline": 8}


def test_steps_per_execution_gathers_the_chunk_in_one_call(zoo_ctx):
    from analytics_zoo_tpu import init_zoo_context

    x, y = _data(4 * 64, 8192)
    want = _estimator(8192).fit(x, y, batch_size=64, epochs=1,
                                verbose=False)
    init_zoo_context(steps_per_execution=2)
    try:
        src = Rows(x)
        snap = METRICS.snapshot()
        got = _estimator(8192).fit(src, y, batch_size=64, epochs=1,
                                   verbose=False)
    finally:
        init_zoo_context()
    # two chunks of 2 x 64 rows, 4 MiB each
    assert _paths(snap) == {"threads": 2, "inline": 2}
    assert got[0]["loss"] == pytest.approx(want[0]["loss"], rel=1e-5)


def test_a_row_that_cannot_be_read_ends_fit_with_its_error(zoo_ctx):
    x, y = _data(4 * 64, 8192)
    est = _estimator(8192)
    with pytest.raises(OSError, match="row 77 cannot be read"):
        est.fit(Rows(x, bad=77), y, batch_size=64, epochs=1, verbose=False)
    # and straight from the helper, whichever piece holds the row
    for bad in (0, 130, 255):
        with pytest.raises(OSError, match=f"row {bad} cannot"):
            gather_rows(Rows(x, bad=bad), np.arange(256))


def test_featureset_batches_go_through_the_same_gather():
    x, y = _data(256, 8192)
    snap = METRICS.snapshot()
    fs = FeatureSet.from_ndarrays(x, y)
    got = list(fs.batches(64, shuffle=True))
    assert len(got) == 4
    rows = np.concatenate([b[0] for b in got])
    assert sorted(map(bytes, rows)) == sorted(map(bytes, x))
    assert _paths(snap) == {
        "native" if native.available() else "threads": 4, "inline": 4}
