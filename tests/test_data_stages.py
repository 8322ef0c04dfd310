"""The data tier and the step loop as the registry and the profiler see
them: ``PrefetchIterator`` times both of its threads per batch in
``data_stage_seconds{stage}``, ``_put_sharded`` counts the bytes it is
handed, a step is a ``train_step_seconds`` sample and no span, and every
stage timed through ``time_stage`` is also a ``zoo:`` host event of a
running ``jax.profiler`` trace, on that trace's clock."""

import os
import sys
import time

import jax
import numpy as np
import pytest

from analytics_zoo_tpu.nn.layers.core import Dense
from analytics_zoo_tpu.nn.topology import Sequential
from analytics_zoo_tpu.observe.metrics import METRICS
from analytics_zoo_tpu.observe.trace import TRACER
from analytics_zoo_tpu.train.estimator import Estimator
from analytics_zoo_tpu.train.prefetch import PrefetchIterator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stages(snap):
    """{stage: histogram} of ``data_stage_seconds`` since ``snap``."""
    prefix = 'data_stage_seconds{stage="'
    return {name[len(prefix):-2]: h
            for name, h in METRICS.delta(snap)["histograms"].items()
            if name.startswith(prefix)}


def _sleepy(n, seconds):
    for i in range(n):
        time.sleep(seconds)
        yield i


def _toy_estimator(width=4):
    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(width,)))
    m.add(Dense(1))
    return Estimator(m, loss="mse")


def _toy_data(n=256, width=4):
    x = np.random.RandomState(0).randn(n, width).astype(np.float32)
    return x, x.sum(axis=1, keepdims=True)


class _SlowRows:
    """Rows as ``fit`` takes them (a shape, fancy indexing) that take a
    while to gather, as rows on a disk do."""

    def __init__(self, a, seconds):
        self.a, self.seconds = a, seconds
        self.shape, self.dtype, self.ndim = a.shape, a.dtype, a.ndim

    def __len__(self):
        return len(self.a)

    def __getitem__(self, idx):
        time.sleep(self.seconds)
        return self.a[idx]


def test_each_stage_is_one_sample_a_batch_and_totals_follow_the_sleeps():
    """The source sleeps 20 ms a batch and the transform 5 ms; the
    consumer does nothing, so it waits for both."""
    def upload(v):
        time.sleep(0.005)
        return v

    n = 6
    snap = METRICS.snapshot()
    assert list(PrefetchIterator(_sleepy(n, 0.02), upload, depth=2)) == \
        list(range(n))
    got = _stages(snap)
    # the probe that finds the source exhausted, and the get that finds
    # the end marker, are one more sample each
    assert got["gather"]["count"] == n + 1
    assert got["upload"]["count"] == n
    assert got["wait"]["count"] == n + 1
    assert got["gather"]["total"] >= n * 0.02
    assert n * 0.005 <= got["upload"]["total"] < got["gather"]["total"]
    # the consumer waited out the producer's whole cycle
    assert got["wait"]["total"] >= got["gather"]["total"]
    assert "queue_full" not in got


def test_no_transform_no_upload_sample():
    snap = METRICS.snapshot()
    assert list(PrefetchIterator(iter(range(3)), depth=2)) == [0, 1, 2]
    got = _stages(snap)
    assert "upload" not in got and got["gather"]["count"] == 4


def test_a_slow_consumer_stalls_the_producer_on_a_full_queue():
    """One sample per item that found the queue full, from then until it
    went in: with a queue of one and a consumer that takes 20 ms an item,
    the stalled items together waited most of the consumer's time."""
    n = 8
    snap = METRICS.snapshot()
    it = PrefetchIterator(iter(range(n)), depth=1)
    for _ in it:
        time.sleep(0.02)
    got = _stages(snap)
    assert 1 <= got["queue_full"]["count"] <= n + 1
    assert got["queue_full"]["total"] >= (n - 3) * 0.02
    assert got["wait"]["total"] < got["queue_full"]["total"]


def test_two_epochs_of_fit_in_the_report(zoo_ctx):
    x, y = _toy_data()
    est = _toy_estimator()
    est.fit(x, y, batch_size=16, epochs=2, verbose=False)
    rep = est.training_report()
    assert rep["last_data_path"] == "host_prefetch"
    delta = rep["metrics_delta"]
    steps = 2 * (len(x) // 16)
    hists = delta["histograms"]
    assert hists['data_stage_seconds{stage="upload"}']["count"] == steps
    assert hists['data_stage_seconds{stage="gather"}']["count"] == steps + 2
    assert hists['data_stage_seconds{stage="wait"}']["count"] == steps + 2
    assert hists['train_step_seconds{kind="1"}']["count"] == steps
    # a batch is 16 rows of 4 float32 features and one float32 target
    assert delta["counters"]["data_upload_bytes_total"] == \
        steps * 16 * (4 + 1) * 4
    chain = TRACER.verify_chain(rep["fit_trace"])
    assert chain["complete"] and chain["terminal"] == "ok"
    names = [s["name"] for s in chain["spans"]]
    assert names.count("train/fit") == 1 and names.count("train/epoch") == 2
    assert "train/step" not in names


@pytest.fixture()
def trace_reduce():
    bench = os.path.join(REPO, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import trace_reduce

    return trace_reduce


def test_one_clock_two_sinks(zoo_ctx, tmp_path, trace_reduce):
    """A fit under the profiler, with the benchmark harness's options and
    read back with its reader: every stage the registry counted is a
    ``zoo:`` host event of the same length, and it lies on the runtime's
    own clock (a dispatch encloses the runtime's call of the step, an
    upload its ``device_put``)."""
    x, y = _toy_data(512, width=2048)
    # a gather of 2 ms and an upload of 128 KB a batch: stages long
    # beside what an annotation costs while the profiler records
    x = _SlowRows(x, 0.002)
    est = _toy_estimator(width=2048)
    est.fit(x, y, batch_size=16, epochs=1, verbose=False)      # compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        snap = METRICS.snapshot()
        est.fit(x, y, batch_size=16, epochs=2, verbose=False)
        jax.block_until_ready(est.params)
        hists = METRICS.delta(snap)["histograms"]
    finally:
        jax.profiler.stop_trace()
    session_ns = (time.perf_counter() - t0) * 1e9
    trace = trace_reduce.load_xplane(
        trace_reduce.find_xplane(str(tmp_path)), min_host_ns=0)
    lines = [l for p in trace.host_planes() for l in p.lines]
    assert lines and not trace.device_planes()      # a CPU run

    def events(name):
        return np.asarray([(s, s + d) for l in lines for s, d, n in
                           zip(l.starts, l.durs, l.names) if n == name],
                          np.int64).reshape(-1, 2)

    def enclosed(outer, inner):
        """How many of ``outer``'s intervals hold one of ``inner``'s."""
        return sum(bool(((inner[:, 0] >= a) & (inner[:, 1] <= b)).any())
                   for a, b in outer)

    everything = np.concatenate([np.stack([l.starts, l.ends], 1)
                                 for l in lines])
    assert everything[:, 1].max() - everything[:, 0].min() <= session_ns
    for series, tag in (
            ('data_stage_seconds{stage="gather"}',
             "zoo:data_stage_seconds/gather"),
            ('data_stage_seconds{stage="upload"}',
             "zoo:data_stage_seconds/upload"),
            ('train_step_seconds{kind="1"}', "zoo:train_step_seconds/1")):
        ev = events(tag)
        assert len(ev) == hists[series]["count"], tag
        assert (ev[:, 1] - ev[:, 0]).sum() / 1e9 == pytest.approx(
            hists[series]["total"], rel=0.2), tag
    steps = events("zoo:train_step_seconds/1")
    assert len(steps) == len(x) // 16
    assert enclosed(steps, events("PjitFunction(step)")) == len(steps)
    uploads = events("zoo:data_stage_seconds/upload")
    assert enclosed(uploads, events("DevicePutWithSharding")) == len(uploads)
    # the two waits are the absence of work and stay off the trace
    assert not any(n.startswith("zoo:") and n.endswith(("/wait",
                                                        "/queue_full"))
                   for l in lines for n in l.names)
