"""Batch assembly: THE row gather of the host data tier.

Reference capability: multi-threaded minibatch assembly
(feature/common/MTSampleToMiniBatch.scala).

``gather_rows(a, idx)`` is ``np.asarray(a[idx])`` for a 1-D index array,
and both host paths assemble their batches through it: ``_fit_arrays``
(train/estimator.py, every shuffled batch and ``K``-step chunk) and
``FeatureSet.batches``.  How it copies is read off its input, never off
an option, and ``data_gather_total{path}`` counts each array gathered:

- ``inline``: under 1 MiB (labels, token ids), or a host with fewer
  than 4 CPUs: the plain fancy index on the calling thread;
- ``native``: a C-contiguous ``ndarray`` (a ``memmap`` is one) with the
  native library built: its threaded ``memcpy``, one copy;
- ``threads``: anything else with a shape and fancy indexing (an HDF5 or
  zarr data set, a user's lazy rows): the index is cut in contiguous
  pieces and pool threads write ``out[lo:hi] = a[idx[lo:hi]]`` into the
  one output.  **Such an array-like is therefore indexed from several
  threads at once**; numpy releases the GIL for the copy, h5py
  serialises under its own lock (safe, no faster).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import numpy as np

from analytics_zoo_tpu import native
from analytics_zoo_tpu.observe import metrics as obs

__all__ = ["gather_rows"]

# below this a batch is copied faster than a thread is woken
_MIN_BYTES = 1 << 20
# as native.gather_rows caps itself
_MAX_THREADS = 8

_pool_lock = threading.Lock()
_pool: Optional[Tuple[ThreadPoolExecutor, int]] = None


def _drop_pool() -> None:
    global _pool
    _pool = None


# a forked child inherits the executor without its threads
os.register_at_fork(after_in_child=_drop_pool)


def _threads() -> Tuple[Optional[ThreadPoolExecutor], int]:
    """The pool and the number of pieces, from the CPUs this process may
    run on.  A piece is copied twice (the fancy index, then into the
    output), so two threads lose to the one inline copy: under 4, no
    pool."""
    global _pool
    with _pool_lock:
        if _pool is None:
            n = min(_MAX_THREADS, len(os.sched_getaffinity(0)))
            _pool = ((ThreadPoolExecutor(n, thread_name_prefix="zoo-gather"),
                      n) if n >= 4 else (None, 1))
        return _pool


def gather_rows(a, idx) -> np.ndarray:
    """``np.asarray(a[idx])`` for a 1-D index array ``idx`` and any ``a``
    with a ``shape``, a ``dtype`` and fancy indexing on its first axis,
    bit for bit; a large batch is copied by several threads."""
    idx = np.asarray(idx)
    row_bytes = np.dtype(a.dtype).itemsize * int(
        np.prod(a.shape[1:], dtype=np.int64))
    if idx.ndim == 1 and row_bytes * len(idx) >= _MIN_BYTES:
        if (isinstance(a, np.ndarray) and a.flags.c_contiguous
                and native.available()):
            obs.count("data_gather_total", path="native")
            return native.gather_rows(a, idx)
        pool, n = _threads()
        if pool is not None:
            obs.count("data_gather_total", path="threads")
            out = np.empty((len(idx),) + tuple(a.shape[1:]), a.dtype)
            per = -(-len(idx) // n)

            def piece(lo: int) -> None:
                out[lo:lo + per] = a[idx[lo:lo + per]]

            # reading the results re-raises a worker's exception here
            list(pool.map(piece, range(0, len(idx), per)))
            return out
    obs.count("data_gather_total", path="inline")
    return np.asarray(a[idx])
