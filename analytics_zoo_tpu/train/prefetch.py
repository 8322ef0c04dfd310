"""Input-pipeline overlap: background-thread batch prefetch.

Reference capability: the reference keeps workers fed via Spark partition
locality + PMEM-cached partitions (feature/FeatureSet.scala:690-722) and
multi-threaded minibatch assembly (feature/common/MTSampleToMiniBatch.scala).

TPU-native design: the host prepares the *next* sharded batch (fancy
indexing, per-batch transforms, ``device_put`` onto the mesh) on a
background thread while the device executes the current step.  JAX
dispatch is asynchronous, so one batch of lookahead is enough to hide
host work; the queue depth is the ``data_prefetch`` config knob.

Both threads' time per batch lands in the registry histogram
``data_stage_seconds{stage}`` (observe/metrics.py):

- producer: ``gather`` (the source's ``next()``: batch assembly, from
  the call of ``data/gather.gather_rows`` until every row is in the
  batch, whether this thread copied them or, for arrays of 1 MiB or
  more, the native library's or the gather pool's threads did: such an
  array-like is indexed from several threads at once),
  ``upload`` (the transform: ``asarray`` + ``device_put``) and
  ``queue_full`` (blocked on a full queue; stalled items only) — the
  three add up to its whole cycle.  ``gather`` and ``upload`` go through
  ``time_stage``, so a running ``jax.profiler`` trace shows them as
  ``zoo:data_stage_seconds/<stage>`` host events beside the device's ops;
- consumer: ``wait`` (blocked on the queue).  A large ``wait`` total
  relative to step time means the input pipeline — not the device — is
  the bottleneck, which is exactly when the DEVICE cache level
  (data/featureset.CacheLevel) pays off; ``queue_full`` is the inverse
  signal.  The two waits are the absence of work and are kept off the
  trace: they would overlap every idle gap whole and hide the stage
  that caused it.

Each end-of-stream probe (the ``next()`` that finds the source
exhausted, the ``get`` that finds the sentinel) is one more sample.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional

from analytics_zoo_tpu.observe import metrics as obs
from analytics_zoo_tpu.robust import faults

logger = logging.getLogger("analytics_zoo_tpu.train")

_SENTINEL = object()


class PrefetchIterator:
    """Wraps an iterator, running it (plus an optional per-item transform)
    on a daemon thread ``depth`` items ahead of the consumer.

    Exceptions raised by the producer are re-raised at the consumption
    point, so failure-retry semantics in the Estimator are preserved.
    """

    def __init__(self, it: Iterable, transform: Optional[Callable] = None,
                 depth: int = 2):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        # producer thread writes _err, the consumer polls it from
        # __next__/_get while the producer may still be running — a
        # plain unlocked field here is the THR-SHARED-MUT race zoolint
        # flags (the reader could act on a half-observed error state)
        self._err_lock = threading.Lock()
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()
        self._closed = False
        self._close_lock = threading.Lock()

        def put_retry(obj) -> bool:
            """Deliver unless the consumer called close(); never drop."""
            stalled_at = None
            while not self._stop.is_set():
                try:
                    # the first try does not block, so that a stall is
                    # timed from its start
                    self._q.put(obj, block=stalled_at is not None,
                                timeout=0.1)
                except queue.Full:
                    if stalled_at is None:
                        # the producer outran the consumer by a full
                        # queue: the inverse signal of stage="wait"
                        stalled_at = time.perf_counter()
                    continue
                if stalled_at is not None:
                    obs.observe("data_stage_seconds",
                                time.perf_counter() - stalled_at,
                                stage="queue_full")
                # qsize() is advisory under concurrency, which is fine
                # for a gauge
                obs.set_gauge("prefetch_queue_depth", self._q.qsize())
                return True
            return False

        def run():
            try:
                src = iter(it)
                while True:
                    with obs.time_stage("data_stage_seconds",
                                        stage="gather"):
                        item = next(src, _SENTINEL)
                    if item is _SENTINEL:
                        break
                    # chaos hook: a planned producer crash surfaces here
                    # exactly like a real data-pipeline failure would
                    faults.inject("prefetch.producer")
                    if transform is not None:
                        with obs.time_stage("data_stage_seconds",
                                            stage="upload"):
                            item = transform(item)
                    if not put_retry(item):
                        return
            except BaseException as e:  # propagate to consumer
                with self._err_lock:
                    self._err = e
            finally:
                # The sentinel must NEVER be dropped: with a short epoch
                # the whole dataset fits in the queue while the consumer
                # sits in its first XLA compile (minutes for big models),
                # and a dropped sentinel leaves the consumer blocked on
                # get() forever once it drains the queue.  Consumers must
                # close() on early exit (the Estimator does) so this
                # retry terminates on abandonment.
                put_retry(_SENTINEL)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        # poll rather than block indefinitely: if the producer thread is
        # gone without its sentinel having been consumed (belt to the
        # suspenders above), surface its error / end-of-iteration instead
        # of hanging the training loop
        t0 = time.perf_counter()
        item = self._get()
        obs.observe("data_stage_seconds", time.perf_counter() - t0,
                    stage="wait")
        obs.set_gauge("prefetch_queue_depth", self._q.qsize())
        if item is _SENTINEL:
            self._thread.join()
            err = self._error()
            if err is not None:
                raise err
            raise StopIteration
        return item

    def _error(self) -> Optional[BaseException]:
        with self._err_lock:
            return self._err

    def _get(self) -> Any:
        while True:
            try:
                return self._q.get(timeout=1.0)
            except queue.Empty:
                if not self._thread.is_alive():
                    try:
                        return self._q.get_nowait()
                    except queue.Empty:
                        err = self._error()
                        if err is not None:
                            raise err
                        raise StopIteration from None

    def close(self, timeout: float = 5.0) -> None:
        """Stop the producer (used on early exit / exception paths).

        Idempotent.  Drains the queue so a producer blocked in
        ``put_retry`` can observe the stop flag, then joins it with a
        bounded ``timeout``: a producer wedged inside the source
        iterator or transform (which Python threads cannot interrupt)
        is surfaced as a logged warning instead of silently leaking —
        the daemon flag still guarantees it cannot block interpreter
        exit."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        self._stop.set()
        deadline = None
        while self._thread.is_alive():
            # keep draining: the producer may have re-filled the queue
            # between our drain and its next put_retry attempt
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
            if not self._thread.is_alive():
                break
            if deadline is None:
                deadline = time.monotonic() + timeout
            elif time.monotonic() > deadline:
                logger.warning(
                    "prefetch producer did not stop within %.1fs of "
                    "close(); it is wedged in the source iterator or "
                    "transform and will be abandoned (daemon thread)",
                    timeout)
                break


def prefetch(it: Iterable, transform: Optional[Callable] = None,
             depth: int = 2) -> Iterable:
    """``depth<=0`` disables prefetching (synchronous passthrough)."""
    if depth <= 0:
        if transform is None:
            return it
        return (transform(x) for x in it)
    return PrefetchIterator(it, transform=transform, depth=depth)
