"""FeatureSet — host-side dataset abstraction with memory tiers.

Reference capability: ``FeatureSet.rdd(memoryType=...)``
(feature/FeatureSet.scala:690-722) with cached index-shuffled partitions
(CachedDistributedFeatureSet:229), disk spilling (DiskFeatureSet:585,
numSlice DISK_AND_DRAM), and PMEM tiers (feature/pmem/).

TPU-native design: there is no RDD — data lives on the *host* as numpy
arrays (DRAM) or memory-mapped .npy slices on disk (DISK_AND_DRAM /
DIRECT), and is fed to the device mesh by the Estimator, which shards each
batch along the data axis.  PMEM has no TPU-host equivalent; the capacity
use-case is covered by the mmap tier.  Transform pipelines
(``Preprocessing`` chains, feature/common/Preprocessing.scala) become
``.transform(fn)`` stages applied lazily per batch on the host.
"""

from __future__ import annotations

import math
import os
import tempfile
from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.data.gather import gather_rows

MemoryType = str  # "DRAM" | "DISK_AND_DRAM" | "DIRECT"


def npy_header(path: str) -> Tuple[Tuple[int, ...], np.dtype]:
    """(shape, dtype) of a .npy file from its header ONLY — no data is
    read or mapped, so the tier auto-router can classify beyond-memory
    datasets without touching their rows."""
    with open(path, "rb") as f:
        version = np.lib.format.read_magic(f)
        shape, _fortran, dtype = np.lib.format._read_array_header(f, version)
    return tuple(int(s) for s in shape), np.dtype(dtype)


class CacheLevel:
    """Where a FeatureSet's rows live while the Estimator trains from it.

    Mirrors the reference's memory tiers (DRAM / PMEM,
    feature/FeatureSet.scala:690-722) translated to TPU hosts: the
    capacity tier there (PMEM) becomes HBM residency here — the fast
    tier is *on the accelerator*, not a slower-but-bigger host medium.

    - ``HOST``: rows stay on the host (numpy / mmap per ``memory_type``);
      batches are assembled per step and ``device_put`` onto the mesh
      (overlapped via train/prefetch.py).
    - ``DEVICE``: the whole dataset is materialized into HBM once and the
      Estimator's device-resident epoch body shuffles and gathers
      minibatches *inside* the compiled step — zero host→device bytes
      per epoch.  Over ``ZooConfig.data_device_budget_bytes`` it
      upgrades to STREAM (or HOST when streaming is not feasible).
    - ``STREAM``: the middle tier for datasets bigger than HBM (the
      reference's PMEM capacity tier, feature/FeatureSet.scala:690-722,
      made TPU-native): the dataset is split into budget-sized shards
      staged on the host, and a background uploader
      (data/streaming.ShardUploader) rotates them through HBM with
      double-buffered async ``device_put`` — shard N+1 uploads while
      the jitted shard program trains on shard N.  Two-level shuffle
      (shard order per epoch, on-device permutation within the shard);
      optional uint8/int8 compressed shards decoded in-kernel
      (``ZooConfig.data_cache_dtype``).
    """

    HOST = "HOST"
    DEVICE = "DEVICE"
    STREAM = "STREAM"

    _LEVELS = (HOST, DEVICE, STREAM)

    @staticmethod
    def normalize(level: str) -> str:
        lv = str(level).upper()
        if lv not in CacheLevel._LEVELS:
            raise ValueError(f"unknown cache level {level!r}; "
                             f"known: {CacheLevel._LEVELS}")
        return lv


class FeatureSet:
    """A set of aligned arrays (inputs..., label) with lazy transforms.

    ``batches(batch_size)`` yields tuples of numpy arrays; the final
    element is the label (if present).
    """

    def __init__(self, arrays: Sequence[np.ndarray],
                 memory_type: MemoryType = "DRAM",
                 transforms: Optional[List[Callable]] = None,
                 seed: int = 0, cache_level: Optional[str] = None):
        if not arrays:
            raise ValueError("FeatureSet needs at least one array")
        n = len(arrays[0])
        for a in arrays:
            if len(a) != n:
                raise ValueError("arrays must be aligned on dim 0")
        self.memory_type = memory_type.upper()
        self.transforms = list(transforms or [])
        self.seed = seed
        self._rng = np.random.RandomState(seed)
        # None = inherit ZooConfig.data_cache_level at fit time
        self.cache_level = (CacheLevel.normalize(cache_level)
                            if cache_level is not None else None)
        if self.memory_type in ("DISK_AND_DRAM", "DIRECT"):
            self.arrays = [self._to_mmap(np.asarray(a)) for a in arrays]
        else:
            self.arrays = [np.asarray(a) for a in arrays]

    # -- constructors (parity with FeatureSet.rdd / ImageSet / TextSet) ---
    @staticmethod
    def from_ndarrays(x, y=None, memory_type: MemoryType = "DRAM",
                      seed: int = 0,
                      cache_level: Optional[str] = None) -> "FeatureSet":
        xs = list(x) if isinstance(x, (list, tuple)) else [x]
        if y is not None:
            xs = xs + [y]
        return FeatureSet(xs, memory_type=memory_type, seed=seed,
                          cache_level=cache_level)

    @staticmethod
    def from_npy_files(paths: Sequence[str],
                       memory_type: MemoryType = "DISK_AND_DRAM"
                       ) -> "FeatureSet":
        mode = "r" if memory_type.upper() != "DRAM" else None
        arrays = [np.load(p, mmap_mode=mode) for p in paths]
        fs = FeatureSet.__new__(FeatureSet)
        fs.memory_type = memory_type.upper()
        fs.transforms = []
        fs.seed = 0
        fs._rng = np.random.RandomState(0)
        fs.cache_level = None
        fs.arrays = list(arrays)
        return fs

    @staticmethod
    def from_parquet(path: str, feature_cols: Sequence[str], label_col: str,
                     memory_type: MemoryType = "DRAM") -> "FeatureSet":
        """Columnar ingestion (replaces the reference's Spark DataFrame
        path, TextSet.readParquet feature/text/TextSet.scala:372)."""
        import pandas as pd  # available via baked-in deps

        df = pd.read_parquet(path)
        arrays = [np.stack(df[c].to_numpy()) for c in feature_cols]
        arrays.append(df[label_col].to_numpy())
        return FeatureSet(arrays, memory_type=memory_type)

    # -- transforms -------------------------------------------------------
    def transform(self, fn: Callable[..., Tuple[np.ndarray, ...]]
                  ) -> "FeatureSet":
        """Append a per-batch transform ``fn(*arrays) -> arrays`` (lazy)."""
        fs = FeatureSet.__new__(FeatureSet)
        fs.arrays = self.arrays
        fs.memory_type = self.memory_type
        fs.transforms = self.transforms + [fn]
        fs.seed = self.seed
        fs._rng = self._rng
        fs.cache_level = self.cache_level
        return fs

    # -- cache levels (HBM residency) -------------------------------------
    def cache(self, level: str = CacheLevel.DEVICE) -> "FeatureSet":
        """Pin this FeatureSet's cache level (``CacheLevel.HOST`` /
        ``DEVICE``), the analog of the reference's
        ``FeatureSet.rdd(memoryType=...)`` tier selection.  Returns a
        shallow copy sharing the backing arrays."""
        fs = FeatureSet.__new__(FeatureSet)
        fs.__dict__.update(self.__dict__)
        fs.cache_level = CacheLevel.normalize(level)
        return fs

    @property
    def nbytes(self) -> int:
        """Total bytes of the backing arrays (the HBM bill of a DEVICE
        cache, pre-transform)."""
        return int(sum(a.dtype.itemsize * a.size for a in self.arrays))

    def device_arrays(self, ctx=None) -> List["Any"]:
        """Materialize the dataset into HBM: one ``device_put`` per array,
        rows sharded over the mesh's data axis when they divide it
        (parallel/sharding.dataset_sharding), replicated otherwise.

        Transforms are applied ONCE here, over the full arrays — valid
        for row-independent (per-sample) transforms, which is what the
        lazy per-batch protocol already implies; transforms that couple
        rows across a batch would change meaning under a different batch
        size too.  The upload is timed under
        ``featureset/device_cache_put`` so the one-off transfer cost is
        visible next to the per-step timings it eliminates.

        Multi-controller: the upload goes through ``device_put_global``,
        whose per-device callback carves out ONLY the row spans this
        process's devices own under ``dataset_sharding`` — each host
        transfers its share of the dataset into its local HBM, and the
        assembled global jax.Array spans the mesh.
        """
        import jax

        from analytics_zoo_tpu.core.context import get_zoo_context
        from analytics_zoo_tpu.core.profiling import timeit
        from analytics_zoo_tpu.parallel.sharding import (
            dataset_sharding, device_put_global)

        ctx = ctx or get_zoo_context()
        arrays = self.arrays
        if self.transforms:
            batch = tuple(np.asarray(a) for a in arrays)
            for fn in self.transforms:
                batch = fn(*batch)
                if not isinstance(batch, tuple):
                    batch = (batch,)
            arrays = list(batch)
        n = len(arrays[0])
        with timeit("featureset/device_cache_put"):
            out = [device_put_global(
                np.asarray(a), dataset_sharding(ctx.mesh, n, np.ndim(a),
                                                axis=ctx.data_axis))
                for a in arrays]
            jax.block_until_ready(out)
        return out

    # -- iteration --------------------------------------------------------
    def __len__(self) -> int:
        return len(self.arrays[0])

    @property
    def size(self) -> int:
        return len(self)

    def batches(self, batch_size: int, shuffle: bool = False,
                drop_remainder: bool = False, pad_to: int = 1,
                shuffle_buffer: Optional[int] = None
                ) -> Iterator[Tuple[np.ndarray, ...]]:
        """Yield batches; ``pad_to`` rounds batch_size up to a multiple
        (device count) so every batch shards evenly over the mesh.

        ``shuffle_buffer`` (config ``shuffle_buffer`` knob) bounds the
        shuffle window: rows are permuted within contiguous blocks of that
        size and the block order is permuted — a locality-preserving
        shuffle so disk-backed tiers (DISK_AND_DRAM/DIRECT mmaps) read
        near-sequentially instead of seeking across the whole file
        (replaces the reference's cached index-shuffled partitions,
        feature/FeatureSet.scala:229).  ``None``/``>=n`` = full
        permutation.
        """
        n = len(self)
        bs = int(math.ceil(batch_size / pad_to)) * pad_to
        if not shuffle:
            order = np.arange(n)
        elif shuffle_buffer is not None and 0 < shuffle_buffer < n:
            buf = int(shuffle_buffer)
            starts = np.arange(0, n, buf)
            self._rng.shuffle(starts)
            order = np.concatenate([
                s + self._rng.permutation(min(buf, n - s)) for s in starts])
        else:
            order = self._rng.permutation(n)
        steps = n // bs if drop_remainder else int(math.ceil(n / bs))
        for s in range(steps):
            idx = order[s * bs:(s + 1) * bs]
            batch = tuple(gather_rows(a, idx) for a in self.arrays)
            for fn in self.transforms:
                batch = fn(*batch)
                if not isinstance(batch, tuple):
                    batch = (batch,)
            yield batch

    def read_rows(self, start: int, stop: int) -> List[np.ndarray]:
        """Row span [start, stop) of every backing array (views for DRAM
        arrays, lazy page-backed reads for mmap tiers) — the shard
        loader for the STREAM tier."""
        if not (0 <= start <= stop <= len(self)):
            raise ValueError(f"row span [{start}, {stop}) out of range "
                             f"for {len(self)} rows")
        return [a[start:stop] for a in self.arrays]

    # -- internals --------------------------------------------------------
    @staticmethod
    def _to_mmap(a: np.ndarray) -> np.ndarray:
        """Spill an array to a disk-backed mmap (DISK_AND_DRAM tier)."""
        fd, path = tempfile.mkstemp(suffix=".npy", prefix="zoo_featureset_")
        os.close(fd)
        np.save(path, a)
        return np.load(path, mmap_mode="r")

    # -- slice-wise disk epochs ------------------------------------------
    @staticmethod
    def from_npy_slices(slices: Sequence[Sequence[str]],
                        seed: int = 0) -> "SlicedFeatureSet":
        """Slice-wise disk training (reference DiskFeatureSet numSlice,
        feature/FeatureSet.scala:585): ``slices`` is a list of aligned
        .npy path tuples; one slice is resident in DRAM at a time and
        epochs stream slice-by-slice (slice order + rows-within-slice
        shuffled), bounding host memory to the largest slice."""
        return SlicedFeatureSet(slices, seed=seed)


class SlicedFeatureSet(FeatureSet):
    """A FeatureSet whose rows live in per-slice .npy files on disk;
    only one slice is materialised in DRAM at a time."""

    def __init__(self, slices: Sequence[Sequence[str]], seed: int = 0):
        if not slices:
            raise ValueError("need at least one slice")
        self.slice_paths = [tuple(s) for s in slices]
        width = len(self.slice_paths[0])
        if any(len(s) != width for s in self.slice_paths):
            raise ValueError("every slice must have the same array count")
        self.memory_type = "DISK_AND_DRAM"
        self.transforms = []
        self.seed = seed
        self._rng = np.random.RandomState(seed)
        # slice-wise sets exist BECAUSE the data outgrows resident memory;
        # HBM caching is never applicable
        self.cache_level = CacheLevel.HOST
        # row counts and byte totals from headers only (no data load,
        # no mmap): classifying a beyond-memory dataset must not cost a
        # page-cache walk over it
        self._slice_rows = []
        self._disk_bytes = 0
        self._row_specs: Optional[List[Tuple[Tuple[int, ...],
                                             np.dtype]]] = None
        for s in self.slice_paths:
            counts = set()
            specs = []
            for p in s:
                shape, dtype = npy_header(p)
                counts.add(shape[0] if shape else 0)
                specs.append((shape[1:], dtype))
                self._disk_bytes += dtype.itemsize * int(
                    np.prod(shape, dtype=np.int64))
            if len(counts) != 1:
                raise ValueError(f"slice {s} arrays are not aligned")
            if self._row_specs is None:
                self._row_specs = specs
            elif specs != self._row_specs:
                raise ValueError(
                    f"slice {s} row shapes/dtypes differ from the first "
                    f"slice: {specs} vs {self._row_specs}")
            self._slice_rows.append(counts.pop())

    def transform(self, fn) -> "SlicedFeatureSet":
        fs = SlicedFeatureSet.__new__(SlicedFeatureSet)
        fs.__dict__.update(self.__dict__)
        fs.transforms = self.transforms + [fn]
        return fs

    @property
    def nbytes(self) -> int:
        """Summed on-disk bytes across slices, computed at __init__ from
        the .npy headers alone (``npy_header``) — no slice is loaded or
        mapped to answer the budget check."""
        return int(self._disk_bytes)

    def cache(self, level: str = CacheLevel.DEVICE) -> "SlicedFeatureSet":
        lv = CacheLevel.normalize(level)
        if lv == CacheLevel.DEVICE:
            raise ValueError(
                "SlicedFeatureSet streams slices because the dataset "
                "outgrows resident memory; a DEVICE (HBM) cache cannot "
                "hold it — use CacheLevel.STREAM to rotate budget-sized "
                "shards through HBM, or FeatureSet.from_ndarrays for "
                "data that fits the device budget")
        fs = SlicedFeatureSet.__new__(SlicedFeatureSet)
        fs.__dict__.update(self.__dict__)
        fs.cache_level = lv
        return fs

    def read_rows(self, start: int, stop: int) -> List[np.ndarray]:
        """Materialize global rows [start, stop) across slice files
        (mmap-backed reads, copied out) — the shard loader for the
        STREAM tier.  Bounded by the requested span, not the slice
        layout."""
        if not (0 <= start <= stop <= len(self)):
            raise ValueError(f"row span [{start}, {stop}) out of range "
                             f"for {len(self)} rows")
        width = len(self.slice_paths[0])
        parts: List[List[np.ndarray]] = [[] for _ in range(width)]
        offset = 0
        for si, rows in enumerate(self._slice_rows):
            lo, hi = max(start - offset, 0), min(stop - offset, rows)
            if lo < hi:
                for j, p in enumerate(self.slice_paths[si]):
                    a = np.load(p, mmap_mode="r")
                    parts[j].append(np.asarray(a[lo:hi]))
            offset += rows
            if offset >= stop:
                break
        return [np.concatenate(ps) if len(ps) > 1 else ps[0]
                for ps in parts]

    def __len__(self) -> int:
        return int(sum(self._slice_rows))

    def batches(self, batch_size: int, shuffle: bool = False,
                drop_remainder: bool = False, pad_to: int = 1,
                shuffle_buffer: Optional[int] = None
                ) -> Iterator[Tuple[np.ndarray, ...]]:
        """Stream batches slice-by-slice.  Rows left over when a slice
        doesn't divide the batch are CARRIED into the next slice (total
        loss per epoch is < one batch, same as the base class), so small
        slices still contribute every row.  ``shuffle_buffer`` is
        accepted but moot here: the resident slice IS the shuffle window
        by construction."""
        bs = int(math.ceil(batch_size / pad_to)) * pad_to
        order = (self._rng.permutation(len(self.slice_paths)) if shuffle
                 else np.arange(len(self.slice_paths)))
        carry: Optional[List[np.ndarray]] = None

        def emit(batch):
            for fn in self.transforms:
                batch = fn(*batch)
                if not isinstance(batch, tuple):
                    batch = (batch,)
            return batch

        for si in order:
            arrays = [np.load(p) for p in self.slice_paths[si]]  # DRAM now
            if carry is not None:
                arrays = [np.concatenate([c, a])
                          for c, a in zip(carry, arrays)]
                carry = None
            n = len(arrays[0])
            rows = self._rng.permutation(n) if shuffle else np.arange(n)
            for s in range(n // bs):
                idx = rows[s * bs:(s + 1) * bs]
                yield emit(tuple(a[idx] for a in arrays))
            rem = rows[(n // bs) * bs:]
            if len(rem):
                carry = [a[rem] for a in arrays]
            del arrays          # release the slice before loading the next
        if carry is not None and not drop_remainder:
            yield emit(tuple(carry))
