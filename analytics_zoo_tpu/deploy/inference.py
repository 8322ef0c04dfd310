"""InferenceModel: multi-backend, thread-safe serving model.

Reference capability: pipeline/inference/InferenceModel.scala:30-72 (a
LinkedBlockingQueue of cloned models provides request concurrency),
loaders for BigDL/Caffe/TF-frozen/TF-SavedModel/PyTorch/OpenVINO
(InferenceModelFactory.scala, ModelLoader.scala), int8 calibrated variants
(InferenceModel.scala:443), predict APIs (:762-830).

TPU-first redesign:
- No clone queue: an XLA-compiled function is immutable and thread-safe,
  so one jitted forward serves any number of threads.  Concurrency policy
  becomes *batching* policy (`DynamicBatcher`).
- Shape buckets: requests are padded up to the next bucket so the number
  of compiled programs stays bounded (replaces per-shape model clones).
- Foreign models: TF SavedModel / tf.keras ingested via
  ``jax2tf.call_tf`` (host TF executes the graph, JAX orchestrates) or —
  preferred — converted to a pure JAX program with imported weights by
  ``tfpark.convert_keras_model``; torch modules run in-process through
  torch (the reference ran libtorch via JNI in-process too).
- INT8: native weight quantization (per-channel symmetric) replacing the
  reference's OpenVINO calibration — int8 tables live in HBM, dequant is
  fused into the consuming matmul by XLA, halving weight bandwidth.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["InferenceModel", "DynamicBatcher", "BatchRequest",
           "ModelReplica", "scatter_batch_results", "quantize_pytree",
           "dequantize_pytree", "plan_buckets", "bucket_class",
           "LONG_DOC_TOKENS", "DEFAULT_MODEL"]

# the implicit model name for single-model serving paths; multi-model
# callers (ClusterServing with a dict of models) use their own names
DEFAULT_MODEL = "default"


def _as_tuple(x):
    return tuple(x) if isinstance(x, (list, tuple)) else (x,)


def imagenet_preprocess(scale: float = 1.0 / 127.5, offset: float = -1.0,
                        dtype=jnp.bfloat16):
    """On-device normalizer for uint8 image wire format: clients send
    raw uint8 HWC images (4x smaller than float32 on the host→device
    link); the chip casts + affine-normalizes inside the serving
    program.  Default maps [0,255] → [-1,1]."""
    def fn(x):
        return x.astype(dtype) * scale + offset

    return fn


def _next_bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


# Requests at or past this many tokens belong to the "long_doc" bucket
# class: attention compute is O(L²)-dominated, so fusing rows into wide
# batch buckets only multiplies an already-saturating program.  Long-doc
# batches plan at the SMALLEST row bucket and the executor routes them
# to a mesh replica whose attention shards L ring-wise over the mesh
# (ops/ring_attention.py) — per-chip memory O(L/ways).
LONG_DOC_TOKENS = 32768


def bucket_class(tokens: Optional[int]) -> str:
    """Which bucket class a request of ``tokens`` sequence length falls
    in: ``"long_doc"`` (>= LONG_DOC_TOKENS) or ``"short"``."""
    return ("long_doc" if tokens is not None
            and int(tokens) >= LONG_DOC_TOKENS else "short")


def plan_buckets(n: int, buckets: Sequence[int],
                 tokens: Optional[int] = None) -> List[tuple]:
    """Split ``n`` rows into ``[(rows, bucket), ...]`` chunks.

    Full ``buckets[-1]``-row chunks first, then one tail chunk padded up
    to its nearest bucket.  This is THE bucket-overflow policy: both the
    compile-shape ledger (`InferenceModel.predict`) and the executor's
    replica dispatch (`serving.DeviceExecutor._dispatch`) plan through
    it, so the set of program shapes they produce can never disagree.

    ``tokens`` (the request's sequence length) selects the bucket class:
    in the ``"long_doc"`` class (>= LONG_DOC_TOKENS) every chunk is the
    SMALLEST row bucket — each sequence-saturated program owns the whole
    mesh replica, and the compiled-shape set stays one program per class
    instead of one per (rows × length) combination.
    """
    if bucket_class(tokens) == "long_doc":
        cap = buckets[0]
        return [(min(n - s, cap), cap) for s in range(0, n, cap)]
    out: List[tuple] = []
    cap = buckets[-1]
    s = 0
    while s < n:
        m = min(n - s, cap)
        out.append((m, _next_bucket(m, buckets)))
        s += m
    return out


def _match_compute_dtype(p, s, xs):
    """A preprocess emitting bf16 (e.g. imagenet_preprocess's uint8→bf16
    wire path) selects bf16 INFERENCE: float params AND state (BN stats)
    cast to the input dtype in-program (XLA folds the casts), outputs
    return as float32 for the client."""
    from analytics_zoo_tpu.train.estimator import _cast_floats

    floats = [x.dtype for x in xs
              if jnp.issubdtype(x.dtype, jnp.floating)]
    cd = jnp.result_type(*floats) if floats else jnp.float32
    if cd != jnp.float32:
        p = _cast_floats(p, cd)
        s = _cast_floats(s, cd)
    return p, s


def _f32_out(out):
    cast = (lambda o: o.astype(jnp.float32)
            if jnp.issubdtype(o.dtype, jnp.floating) else o)
    return ([cast(o) for o in out]
            if isinstance(out, (list, tuple)) else cast(out))


# ---------------------------------------------------------------------------
# int8 weight quantization (reference InferenceModel.scala:443 — OpenVINO
# int8 calibration — replaced by a native AQT-style pass)
# ---------------------------------------------------------------------------

def quantize_pytree(params, min_size: int = 1024, bits: int = 8):
    """Per-channel symmetric quantization of float leaves.

    ``bits=8``: each quantized leaf becomes ``{"q": int8 array, "scale":
    f32 per-last-axis-channel}``.  ``bits=4``: 2-D leaves with an even
    row count become ``{"q4": nibble-packed int8, "scale": f32}`` at 1/8
    the f32 footprint (ops/dequant_matmul.pack_int4); other leaves keep
    the int8 scheme (int4 packs along the contraction axis, which only
    a matmul weight has).  Small or non-float leaves pass through
    unchanged.  The leaf KEY ("q" vs "q4") carries the storage format —
    pytree structure stays static under jit, so the serving forward can
    route on it.
    """
    from analytics_zoo_tpu.ops.dequant_matmul import quantize_weights
    from analytics_zoo_tpu.ops.quantization import quantize_tensor

    def one(leaf):
        a = np.asarray(leaf)
        if a.dtype.kind != "f" or a.size < min_size or a.ndim == 0:
            return leaf
        if bits == 4 and a.ndim == 2 and a.shape[0] % 2 == 0:
            q4, scale = quantize_weights(a, bits=4)
            return {"q4": np.asarray(q4),
                    "scale": np.asarray(scale, np.float32)}
        # per-channel (last axis) for >=2-D; 1-D uses the same machinery
        # with its single axis (ONE shared int8 scheme — see
        # ops/quantization.quantize_tensor)
        if a.ndim >= 2:
            q, scale = quantize_tensor(a, axis=-1)
        else:
            amax = np.max(np.abs(a))
            scale = jnp.asarray([amax / 127.0 if amax > 0 else 1.0],
                                jnp.float32)
            q = jnp.clip(jnp.round(jnp.asarray(a) / scale), -127,
                         127).astype(jnp.int8)
        return {"q": np.asarray(q), "scale": np.asarray(scale, np.float32)}

    return jax.tree_util.tree_map(one, params)


def _is_qleaf(x) -> bool:
    return (isinstance(x, dict)
            and set(x) in ({"q", "scale"}, {"q4", "scale"}))


def dequantize_pytree(qparams):
    """Inverse of quantize_pytree — runs inside jit so XLA fuses the
    int8→f32 dequant into the consuming matmul (weights stay int8 in HBM)."""
    from analytics_zoo_tpu.ops.dequant_matmul import unpack_int4

    def one(x):
        if not _is_qleaf(x):
            return x
        if "q4" in x:  # zoolint: disable=JG-TRACED-BRANCH(dict-key membership is static pytree structure, not a traced value)
            q = unpack_int4(x["q4"], 2 * x["q4"].shape[0])
            return q.astype(jnp.float32) * x["scale"]
        return x["q"].astype(jnp.float32) * x["scale"]

    return jax.tree_util.tree_map(one, qparams, is_leaf=_is_qleaf)


def _dense_layer_names(net) -> set:
    """Names of Dense layers in a net — their quantized kernels stay
    packed through the serving forward (Dense fuses the dequant into the
    matmul via ops/dequant_matmul.py); every other quantized leaf is
    dequantized up front."""
    from analytics_zoo_tpu.nn.layers.core import Dense

    try:
        return {lyr.name for lyr in net.layers if isinstance(lyr, Dense)}
    except Exception:
        return set()


def _dequant_for_forward(qparams, dense_names):
    """Dequantize quantized leaves, EXCEPT Dense kernels, which pass
    through as q-leaves for the fused dequantize-matmul path."""
    if not isinstance(qparams, dict):
        return dequantize_pytree(qparams)
    out = {}
    for lname, sub in qparams.items():
        if lname in dense_names and isinstance(sub, dict):  # zoolint: disable=JG-TRACED-BRANCH(layer names are static python strings, not traced values)
            out[lname] = {
                k: (v if k == "kernel" and _is_qleaf(v)
                    else dequantize_pytree(v))
                for k, v in sub.items()}
        else:
            out[lname] = dequantize_pytree(sub)
    return out


# ---------------------------------------------------------------------------
# InferenceModel
# ---------------------------------------------------------------------------

class ModelReplica:
    """One serving replica: ``dispatch(xs)`` enqueues the computation and
    returns a handle immediately (device futures for native models);
    ``harvest(handle)`` performs the blocking readback and returns a list
    of np output arrays.  The split is what lets the device executor
    double-buffer: dispatch batch N+1 while N's readback is in flight."""

    def __init__(self, dispatch: Callable, harvest: Callable, device=None,
                 on_device_topn: bool = False, pads_input: bool = True):
        self.dispatch = dispatch
        self.harvest = harvest
        self.device = device
        self.on_device_topn = on_device_topn
        # False = dispatch() already handles buckets/slicing (the shared
        # predict() fallback); True = the executor pads to a bucket
        self.pads_input = pads_input

class InferenceModel:
    """Thread-safe model for serving.

    Construct via one of the loaders::

        m = InferenceModel.load("/path/saved_by_save_model")   # native
        m = InferenceModel.from_keras_net(net, params, state)  # in-process
        m = InferenceModel.load_tf_saved_model(path)           # TF ingest
        m = InferenceModel.load_torch(path_or_module)          # torch

    then ``m.predict(inputs)`` from any number of threads.
    """

    def __init__(self, forward: Callable, batch_buckets: Sequence[int] =
                 (1, 8, 64, 256), dtype=None, name: str = DEFAULT_MODEL):
        """``forward``: fn(list_of_np_inputs_padded) -> np output(s) for a
        full padded batch.  Wrapped by bucket padding in predict().
        ``name`` labels this model's series in every serving metric."""
        self._forward = forward
        self.batch_buckets = tuple(sorted(batch_buckets))
        self.dtype = dtype
        self.name = str(name)
        # program-shape ledger: one entry per distinct batch signature
        # actually dispatched that paid a LIVE XLA compile.  Tests assert
        # on it to prove the bounded-program contract (novel large
        # batches split into full-bucket programs instead of compiling
        # one-off shapes).  Signatures pre-installed from the persistent
        # compile cache land in ``_warm_shapes`` instead, so a warm
        # restart holds ``compile_count == 0`` — the warm-start proof.
        self._seen_shapes = set()
        self._warm_shapes = set()
        self._shape_lock = threading.Lock()
        self._net = None
        self._weight_dtype = "float32"
        # persistent AOT compile cache (deploy/compile_cache.py):
        # attached via attach_compile_cache(); _programs maps a JSON sig
        # key to a loaded/compiled executable
        self._cache = None
        self._fingerprint_cache: Optional[str] = None
        self._programs: Dict[str, Any] = {}
        self._param_fwds: Dict[Any, Any] = {}
        self._programs_lock = threading.Lock()
        self._pred_weights = None

    # expose the bucket lowering on the class (callers/tests reach it as
    # InferenceModel._next_bucket)
    _next_bucket = staticmethod(_next_bucket)

    def _note_shapes(self, xs, tag: str = "") -> bool:
        """Record the batch signature about to be dispatched; True (and a
        ``inference/novel_batch_shape`` counter bump) on first sight —
        i.e. when this dispatch pays an XLA compile.  Signatures the
        compile cache pre-installed (``warm()``) are not novel: their
        executable is already resident, no compile is paid."""
        sig = (tag,) + tuple((tuple(np.shape(x)),
                              str(getattr(x, "dtype", ""))) for x in xs)
        with self._shape_lock:
            if sig in self._seen_shapes or sig in self._warm_shapes:
                return False
            self._seen_shapes.add(sig)
            live = len(self._seen_shapes)
        from analytics_zoo_tpu.observe import metrics as obs

        obs.count("inference_novel_batch_shapes_total", model=self.name,
                  flat="inference/novel_batch_shape")
        obs.set_gauge("inference_compile_count", live, model=self.name)
        return True

    @property
    def compile_count(self) -> int:
        """Number of distinct program shapes that paid a live compile
        (cache-warmed shapes excluded)."""
        with self._shape_lock:
            return len(self._seen_shapes)

    @property
    def warm_count(self) -> int:
        """Number of program shapes pre-installed from the compile cache."""
        with self._shape_lock:
            return len(self._warm_shapes)

    # -- persistent AOT compile cache --------------------------------------
    def fingerprint(self) -> str:
        """Content hash of this model's weights: net class + weight dtype
        + per-leaf (path, shape, dtype, CRC32 of the bytes).  The compile
        cache keys on it so an executable can never be replayed against
        different weights/architecture than it was compiled for."""
        if self._fingerprint_cache is not None:
            return self._fingerprint_cache
        import hashlib
        import struct
        import zlib

        h = hashlib.sha256()
        h.update((type(self._net).__name__ if self._net is not None
                  else "<fn>").encode())
        h.update(self._weight_dtype.encode())
        weights = (self._qparams if getattr(self, "_int8", False)
                   else getattr(self, "_params", None))
        for tree in (weights, getattr(self, "_state", None)):
            if tree is None:
                continue
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                a = np.asarray(leaf)
                h.update(jax.tree_util.keystr(path).encode())
                h.update(str(a.shape).encode())
                h.update(str(a.dtype).encode())
                h.update(struct.pack(
                    "<I", zlib.crc32(a.tobytes()) & 0xFFFFFFFF))
        self._fingerprint_cache = h.hexdigest()[:16]
        return self._fingerprint_cache

    def weight_nbytes(self) -> int:
        """Per-replica HBM weight footprint — what the multi-model HBM
        budget (`serving_hbm_budget_bytes`) charges per replica slot.
        Function/foreign models have no explicit weight tree: 0."""
        weights = (self._qparams if getattr(self, "_int8", False)
                   else getattr(self, "_params", None))
        if weights is None:
            return 0
        total = 0
        for leaf in jax.tree_util.tree_leaves(weights):
            total += np.asarray(leaf).nbytes
        for leaf in jax.tree_util.tree_leaves(getattr(self, "_state", None)
                                              or {}):
            total += np.asarray(leaf).nbytes
        return total

    def attach_compile_cache(self, cache, name: Optional[str] = None
                             ) -> "InferenceModel":
        """Wire a ``deploy.compile_cache.CompileCache`` into the dispatch
        path: every bucketed program is AOT-lowered
        (``fwd.lower(...).compile()``), persisted on first compile, and
        reloaded from disk on the next process start (``warm()``).

        Only models with a native net qualify — foreign forwards
        (TF/torch/function) have no param-explicit program to serialize.
        """
        if self._net is None:
            raise ValueError(
                "attach_compile_cache needs a native net (from_keras_net/"
                "load); TF/torch/function models have no param-explicit "
                "XLA program to serialize")
        self._cache = cache
        if name:
            self.name = str(name)
        return self

    @staticmethod
    def _aot_sig(xs, device, top_n) -> Dict[str, Any]:
        """JSON-able program signature: input shapes/dtypes + target
        device + fused top-N.  Joined with ``fingerprint()`` (and the
        mesh descriptor, added by the cache) it addresses one executable."""
        return {"in": [[list(np.shape(x)), str(getattr(x, "dtype", ""))]
                       for x in xs],
                "dev": str(device) if device is not None else "",
                "top_n": int(top_n or 0)}

    @staticmethod
    def _warm_sig(sig: Dict[str, Any]):
        """The ``_note_shapes`` ledger key a cached sig corresponds to."""
        return ((sig.get("dev", ""),)
                + tuple((tuple(s), d) for s, d in sig["in"]))

    def _param_forward_for(self, top_n):
        with self._programs_lock:
            fwd = self._param_fwds.get(top_n)
            if fwd is None:
                fwd = self._build_param_forward(top_n=top_n)
                self._param_fwds[top_n] = fwd
        return fwd

    def _aot_program(self, p, s, xs, device=None, top_n=None, fwd=None):
        """The executable for one program signature: in-memory table →
        disk cache → live ``lower().compile()`` (which is then persisted
        so the NEXT process start skips it).  ``fwd`` overrides which
        jitted forward lowers on a miss (the sharded mesh-replica path
        traces with its table mode baked in; its ``device`` descriptor
        keeps the cache entries distinct)."""
        sig = self._aot_sig(xs, device, top_n)
        import json
        key = json.dumps(sig, sort_keys=True)
        with self._programs_lock:
            prog = self._programs.get(key)
        if prog is not None:
            return prog
        prog = self._cache.load(self.fingerprint(), sig, model=self.name)
        if prog is None:
            if fwd is None:
                fwd = self._param_forward_for(top_n)
            prog = fwd.lower(p, s, *xs).compile()
            self._cache.store(self.fingerprint(), sig, prog,
                              model=self.name)
        with self._programs_lock:
            self._programs[key] = prog
        return prog

    def warm(self) -> int:
        """Pre-install every cached executable for this model's
        fingerprint.  A restarted process reaches full bucket coverage
        here, in deserialization time, instead of after N live compiles
        — and ``compile_count`` stays 0 for every warmed shape
        (``tests/test_compile_cache.py`` asserts it across processes).
        Returns the number of programs installed."""
        if self._cache is None:
            return 0
        import json
        n = 0
        for sig, prog in self._cache.load_all(self.fingerprint(),
                                              model=self.name):
            key = json.dumps(sig, sort_keys=True)
            with self._programs_lock:
                self._programs[key] = prog
            with self._shape_lock:
                self._warm_shapes.add(self._warm_sig(sig))
            n += 1
        return n

    # -- loaders -----------------------------------------------------------
    @classmethod
    def load(cls, path: str, int8: bool = False,
             weight_dtype: Optional[str] = None, **kw) -> "InferenceModel":
        """Load the native format written by ``ZooModel.save_model`` (a dir
        with config.json + weights.npz) — reference doLoad
        (InferenceModel.scala:86)."""
        from analytics_zoo_tpu.models.common import ZooModel

        zm = ZooModel.load_model(path)
        net = zm.model
        tree = getattr(zm, "_pending_weights", None)
        if tree is None:
            raise FileNotFoundError(f"{path} has no weights.npz")
        return cls.from_keras_net(net, tree["params"], tree.get("state", {}),
                                  int8=int8, weight_dtype=weight_dtype, **kw)

    @staticmethod
    def _resolve_weight_dtype(weight_dtype: Optional[str],
                              int8: bool) -> str:
        """None defers to the legacy ``int8`` flag, then to the global
        ``serving_weight_dtype`` knob (no context = float32)."""
        if weight_dtype is None:
            if int8:
                return "int8"
            from analytics_zoo_tpu.ops.dispatch import config_knob

            weight_dtype = config_knob("serving_weight_dtype", "float32")
        if weight_dtype not in ("float32", "int8", "int4"):
            raise ValueError(
                f"serving weight_dtype must be float32|int8|int4, got "
                f"{weight_dtype!r}")
        return weight_dtype

    @classmethod
    def from_keras_net(cls, net, params, state=None, int8: bool = False,
                       preprocess: Optional[Callable] = None,
                       weight_dtype: Optional[str] = None,
                       **kw) -> "InferenceModel":
        """Wrap a built KerasNet + weights as a serving model.

        ``preprocess``: optional jax fn run ON DEVICE inside the same
        compiled program as the forward pass (fn(*raw) -> model input(s)).
        Lets clients ship compact wire dtypes — e.g. uint8 images
        normalized on-chip — so the host→device link carries 4x fewer
        bytes than float32 (see ``deploy.imagenet_preprocess``).

        ``weight_dtype``: replica weight storage — "float32", "int8"
        (1/4 HBM footprint) or "int4" (1/8); ``None`` resolves the
        legacy ``int8`` flag, then the ``serving_weight_dtype`` config
        knob.  Quantized Dense kernels stay packed end-to-end: the
        forward dequantizes them inside the matmul
        (ops/dequant_matmul.py — the fused Pallas kernel on TPU)."""
        state = state or {}
        weight_dtype = cls._resolve_weight_dtype(weight_dtype, int8)
        quantized = weight_dtype != "float32"
        qparams = (quantize_pytree(params,
                                   bits=4 if weight_dtype == "int4" else 8)
                   if quantized else None)
        dense_names = _dense_layer_names(net) if quantized else set()

        if quantized:
            @jax.jit
            def fwd(*xs):
                if preprocess is not None:
                    xs = _as_tuple(preprocess(*xs))
                p, s2 = _match_compute_dtype(
                    _dequant_for_forward(qparams, dense_names), state, xs)
                out, _ = net.call(p, s2, *xs, training=False)
                return _f32_out(out)
        else:
            @jax.jit
            def fwd(*xs):
                if preprocess is not None:
                    xs = _as_tuple(preprocess(*xs))
                p, s2 = _match_compute_dtype(params, state, xs)
                out, _ = net.call(p, s2, *xs, training=False)
                return _f32_out(out)

        def forward(inputs: List[np.ndarray]):
            return fwd(*[jnp.asarray(x) for x in inputs])

        m = cls(forward, **kw)
        m._net, m._params, m._int8 = net, params, quantized
        m._weight_dtype = weight_dtype
        m._state, m._preprocess, m._qparams = state, preprocess, qparams
        return m

    # -- replicas ----------------------------------------------------------
    def _build_param_forward(self, top_n: Optional[int] = None,
                             table_shard=None):
        """One jitted forward taking (params, state, *xs) explicitly, so
        the same traced program runs on whichever device its arguments
        live on — the building block for per-device serving replicas.
        ``top_n`` fuses top-k into the program (scores never leave the
        chip: the readback is 2*top_n scalars per row, not the logits).
        ``table_shard`` (a ``parallel.mode.TableShardMode``) is entered
        INSIDE the traced body, so the listed embedding tables lower to
        the ``shard_map`` local-bag + psum exchange at trace time —
        the mesh-replica forward for row-sharded giant tables."""
        import contextlib

        net, pre, int8 = self._net, self._preprocess, self._int8
        dense_names = _dense_layer_names(net) if int8 else set()
        if table_shard is not None:
            from analytics_zoo_tpu.parallel.mode import table_mode
        else:
            table_mode = None

        @jax.jit
        def fwd(p, s, *xs):
            ctx = (table_mode(table_shard) if table_shard is not None
                   else contextlib.nullcontext())
            with ctx:
                if pre is not None:
                    xs = _as_tuple(pre(*xs))
                if int8:
                    p = _dequant_for_forward(p, dense_names)
                p2, s2 = _match_compute_dtype(p, s, xs)
                out, _ = net.call(p2, s2, *xs, training=False)
                out = _f32_out(out)
                if top_n:
                    o = out[0] if isinstance(out, (list, tuple)) else out
                    v, i = jax.lax.top_k(o, top_n)
                    return i.astype(jnp.int32), v
                return out

        return fwd

    def replica_forwards(self, n: int = 1, devices=None,
                         top_n: Optional[int] = None
                         ) -> List["ModelReplica"]:
        """``n`` per-device serving replicas with *async* dispatch.

        Models built from a native net (``from_keras_net`` / ``load``)
        get true replicas: the weights are placed once per device and
        each dispatch runs on its own chip, so a round-robin executor
        keeps every chip busy.  Foreign loaders (TF/torch/ONNX/function)
        fall back to sharing the base forward — it is thread-safe, just
        not multi-device.
        """
        if devices is None:
            from analytics_zoo_tpu.core import context as _context
            from analytics_zoo_tpu.parallel.sharding import replica_devices

            ctx = _context._GLOBAL_CONTEXT
            devices = (replica_devices(ctx.mesh) if ctx is not None
                       else jax.devices())
        devices = list(devices)[:max(1, int(n))]
        if self._net is None:
            # shared-forward fallback: predict() handles buckets/top-N
            model = self

            def dispatch(xs, _m=model):
                return _m.predict(xs)

            def harvest(h):
                return h if isinstance(h, list) else [h]

            return [ModelReplica(dispatch, harvest, device=None,
                                 on_device_topn=False, pads_input=False)
                    for _ in devices]
        fwd = self._build_param_forward(top_n=top_n)
        weights = self._qparams if self._int8 else self._params
        out = []
        for dev in devices:
            p_i = jax.device_put(weights, dev)
            s_i = jax.device_put(self._state, dev)

            def dispatch(xs, _p=p_i, _s=s_i, _d=dev):
                # async: device_put and the jitted call both return
                # immediately with future-backed arrays — readback (the
                # only blocking part) happens in harvest()
                self._note_shapes(xs, tag=str(_d))
                xd = [jax.device_put(jnp.asarray(x), _d) for x in xs]
                if self._cache is not None:
                    prog = self._aot_program(_p, _s, xd, device=_d,
                                             top_n=top_n)
                    return prog(_p, _s, *xd)
                return fwd(_p, _s, *xd)

            def harvest(h):
                hs = h if isinstance(h, (list, tuple)) else [h]
                return [np.asarray(o) for o in hs]

            out.append(ModelReplica(dispatch, harvest, device=dev,
                                    on_device_topn=bool(top_n),
                                    pads_input=True))
        return out

    def mesh_replica(self, mesh, top_n: Optional[int] = None
                     ) -> "ModelReplica":
        """One serving replica spanning a whole ``Mesh`` — the
        long-document executor slot (docs/SERVING.md "Long-document
        bucket class").  Weights are placed replicated over the mesh
        once; each dispatch runs the forward with all mesh devices
        cooperating, so a net whose attention shards the sequence axis
        (``seq_shards`` → ops/ring_attention.py) holds only O(L/ways)
        of K/V per chip instead of the full 32k–128k context.  The AOT
        compile-cache signature carries a device descriptor, so the
        mesh program warms independently of the single-chip buckets.
        """
        if self._net is None:
            raise ValueError(
                "mesh_replica needs a native net (from_keras_net/load); "
                "foreign forwards have no mesh-placeable param tree")
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        fwd = self._build_param_forward(top_n=top_n)
        weights = self._qparams if self._int8 else self._params
        p_i = jax.device_put(weights, rep)
        s_i = jax.device_put(self._state, rep)
        desc = "mesh:" + "x".join(
            f"{k}={v}" for k, v in mesh.shape.items())

        def dispatch(xs):
            self._note_shapes(xs, tag=desc)
            xd = [jax.device_put(jnp.asarray(x), rep) for x in xs]
            if self._cache is not None:
                prog = self._aot_program(p_i, s_i, xd, device=desc,
                                         top_n=top_n)
                return prog(p_i, s_i, *xd)
            return fwd(p_i, s_i, *xd)

        def harvest(h):
            hs = h if isinstance(h, (list, tuple)) else [h]
            return [np.asarray(o) for o in hs]

        return ModelReplica(dispatch, harvest, device=desc,
                            on_device_topn=bool(top_n), pads_input=True)

    def sharded_tables(self) -> tuple:
        """The net's row-shardable table manifest (layer names set by
        ``table_placement="sharded"`` model builders), or () for nets
        without one."""
        return tuple(getattr(self._net, "_sharded_tables", None) or ())

    def weight_nbytes_per_chip(self, mesh, axis: str = "model") -> int:
        """PER-CHIP HBM weight footprint when this model serves as a
        mesh replica over ``mesh``: listed tables charge
        ``nbytes / ways`` (they row-shard over ``axis``), everything
        else charges full bytes.  This is what the executor's HBM
        budget planner charges a mesh-replica slot — the whole point of
        the sharded serving path is that a table bigger than one chip's
        budget still fits per-chip."""
        tables = self.sharded_tables()
        weights = self._qparams if getattr(self, "_int8", False) \
            else getattr(self, "_params", None)
        if weights is None:
            return 0
        if not tables or mesh is None:
            return self.weight_nbytes()
        from analytics_zoo_tpu.parallel.table_sharding import \
            per_chip_weight_nbytes
        total = per_chip_weight_nbytes(weights, tables, mesh, axis=axis)
        total += per_chip_weight_nbytes(
            getattr(self, "_state", None) or {}, tables, mesh, axis=axis)
        return total

    # -- hot-row replication caches (ISSUE 19) -----------------------------
    def _table_leaf(self, tname: str):
        """The authoritative ``<tname>/table`` param leaf, or None."""
        from analytics_zoo_tpu.parallel.sharding import path_str
        from analytics_zoo_tpu.parallel.table_sharding import \
            table_leaf_patterns

        pats = table_leaf_patterns((tname,))
        found = [None]

        def one(path, leaf):
            if any(p.search(path_str(path)) for p in pats):
                found[0] = leaf
            return leaf

        jax.tree_util.tree_map_with_path(
            one, getattr(self, "_params", None) or {})
        return found[0]

    def _table_id_field_indices(self, tname: str,
                                id_fields=None) -> Optional[tuple]:
        """Input positions whose arrays carry ``tname``'s id stream:
        an explicit ``id_fields`` entry wins, then the net's
        ``_table_id_fields`` manifest, then the graph-ancestor trace
        (``Model.input_ancestors``).  None means "unknown" — the
        caller falls back to every integer input."""
        names = None
        if id_fields and tname in id_fields:
            names = tuple(id_fields[tname])
        else:
            manifest = getattr(self._net, "_table_id_fields", None) or {}
            if tname in manifest:
                names = tuple(manifest[tname])
            elif hasattr(self._net, "input_ancestors"):
                # an empty trace means the manifest names a layer the
                # graph doesn't apply — treat as unknown, not as "no
                # id stream", so the cache still fills
                names = self._net.input_ancestors(tname) or None
        if names is None:
            return None
        inputs = [v.name for v in getattr(self._net, "inputs", [])]
        return tuple(i for i, n in enumerate(inputs) if n in names)

    def enable_hot_caches(self, mesh=None, *, axis: str = "model",
                          capacity: Optional[int] = None,
                          refresh_period_s: Optional[float] = None,
                          id_fields: Optional[Dict[str, Any]] = None,
                          clock=time.monotonic) -> Dict[str, Any]:
        """Build one :class:`~analytics_zoo_tpu.parallel.hot_cache.
        HotRowCache` per entry of the net's ``_sharded_tables`` manifest
        (the ``table_hot_cache`` knob gates this: ``"off"`` builds
        none).  The caches are SERVING-side and read-only: frequency
        fills from the dispatch id streams (``record_hot_ids``), values
        come only from ``refresh_hot_caches`` re-reading the
        authoritative params, and ``invalidate_hot_caches`` runs on
        every ``swap_replicas`` / hot reload.  ``clock`` is injectable
        for the staleness tests.

        Each cache records only its OWN table's id streams: the input
        fields feeding a table come from ``id_fields`` (table name ->
        input-field names), the net's ``_table_id_fields`` manifest, or
        the graph-ancestor trace — so a multi-table model's caches
        never cross-pollute, and integer non-id inputs (lengths,
        offsets, positions) never skew a ranking."""
        from analytics_zoo_tpu.ops.dispatch import config_knob
        from analytics_zoo_tpu.parallel.hot_cache import HotRowCache

        if config_knob("table_hot_cache", "auto") == "off":
            self._hot_caches: Dict[str, Any] = {}
            self._hot_cache_fields: Dict[str, Any] = {}
            return {}
        if capacity is None:
            capacity = int(config_knob("table_hot_cache_capacity", 1024))
        if refresh_period_s is None:
            refresh_period_s = float(
                config_knob("table_hot_cache_refresh_s", 30.0))
        caches: Dict[str, Any] = {}
        fields: Dict[str, Any] = {}
        for tname in self.sharded_tables():
            leaf = self._table_leaf(tname)
            if leaf is None or len(getattr(leaf, "shape", ())) != 2:
                continue
            caches[tname] = HotRowCache(
                f"{self.name}/{tname}", capacity,
                dim=int(leaf.shape[1]),
                refresh_period_s=refresh_period_s, clock=clock,
                mesh=mesh,
                dtype=np.dtype(str(getattr(leaf, "dtype", "float32"))))
            fields[tname] = self._table_id_field_indices(
                tname, id_fields)
        self._hot_caches = caches
        self._hot_cache_fields = fields
        return dict(caches)

    def hot_caches(self) -> Dict[str, Any]:
        return dict(getattr(self, "_hot_caches", None) or {})

    def record_hot_ids(self, xs) -> None:
        """Fold a dispatch batch's id streams into the table caches'
        frequency counts — each cache sees only the input positions
        mapped to ITS table (``enable_hot_caches``); a table with no
        known mapping falls back to every integer array."""
        caches = getattr(self, "_hot_caches", None)
        if not caches:
            return
        fields = getattr(self, "_hot_cache_fields", None) or {}
        arrays = [np.asarray(x) for x in xs]
        int_idx = [i for i, a in enumerate(arrays)
                   if a.dtype.kind in "iu"]
        for tname, c in caches.items():
            idx = fields.get(tname)
            for i in (int_idx if idx is None
                      else [i for i in idx if i in int_idx]):
                c.record(arrays[i])

    def refresh_hot_caches(self, force: bool = False) -> int:
        """Re-rank + re-read every cache from the authoritative table
        leaves; ``force`` skips the period check (used right after a
        weight swap).  Returns the number of caches refreshed."""
        from analytics_zoo_tpu.parallel.hot_cache import table_row_reader

        done = 0
        for tname, cache in self.hot_caches().items():
            leaf = self._table_leaf(tname)
            if leaf is None:
                continue
            reader = table_row_reader(leaf)
            if force:
                cache.refresh(reader)
                done += 1
            elif cache.maybe_refresh(reader):
                done += 1
        return done

    def invalidate_hot_caches(self, reason: str = "swap") -> None:
        """Drop every cache's replica rows (all ids miss until the next
        refresh) — the weight-swap safety hook: a hot-reloaded model
        must never serve pre-swap rows."""
        for cache in self.hot_caches().values():
            cache.invalidate(reason)

    def shard_replica(self, mesh, top_n: Optional[int] = None,
                      axis: str = "model") -> "ModelReplica":
        """One serving replica spanning a whole ``Mesh`` with the net's
        ``_sharded_tables`` row-sharded ``P(axis, None)`` over it — the
        giant-embedding serving path (docs/SERVING.md "Pod-scale
        serving").

        Each listed table leaf is placed once with ``rows/ways`` rows
        per chip; every other leaf replicates.  The forward traces with
        the table-shard mode active, so ``ShardedEmbeddingTable``
        lowers to ``parallel.table_sharding.sharded_bag`` — the local
        fused lookup plus ONE ``(B, D)`` psum per table; the gathered
        rows never leave their owning shard.  The AOT compile-cache
        signature carries a ``shard_mesh:...`` device descriptor (and
        the cache env already folds in the mesh), so a rebuilt mesh
        replica warm-starts with zero live compiles.
        """
        if self._net is None:
            raise ValueError(
                "shard_replica needs a native net (from_keras_net/load); "
                "foreign forwards have no mesh-placeable param tree")
        from jax.sharding import NamedSharding, PartitionSpec

        from analytics_zoo_tpu.parallel.mode import TableShardMode
        from analytics_zoo_tpu.parallel.sharding import path_str
        from analytics_zoo_tpu.parallel.table_sharding import (
            resolve_table_ways, table_leaf_patterns)

        tables = self.sharded_tables()
        mode = TableShardMode(mesh, axis, tables)
        rep = NamedSharding(mesh, PartitionSpec())
        row_sh = NamedSharding(mesh, PartitionSpec(axis, None))
        pats = table_leaf_patterns(tables)

        def placement(path, leaf):
            shape = getattr(leaf, "shape", ())
            if (any(p.search(path_str(path)) for p in pats)
                    and len(shape) == 2
                    and resolve_table_ways(mesh, axis,
                                           int(shape[0])) > 1):
                return row_sh
            return rep

        fwd = self._build_param_forward(top_n=top_n, table_shard=mode)
        weights = self._qparams if self._int8 else self._params
        shardings = jax.tree_util.tree_map_with_path(placement, weights)
        p_i = jax.device_put(weights, shardings)
        s_i = jax.device_put(self._state, rep)
        desc = ("shard_mesh:" + "x".join(
            f"{k}={v}" for k, v in mesh.shape.items()) + f":{axis}")

        def dispatch(xs):
            self._note_shapes(xs, tag=desc)
            # hot-row cache frequency tap: the fused id streams passing
            # through here ARE the batcher's traffic (host numpy still)
            self.record_hot_ids(xs)
            xd = [jax.device_put(jnp.asarray(x), rep) for x in xs]
            if self._cache is not None:
                prog = self._aot_program(p_i, s_i, xd, device=desc,
                                         top_n=top_n, fwd=fwd)
                return prog(p_i, s_i, *xd)
            return fwd(p_i, s_i, *xd)

        def harvest(h):
            hs = h if isinstance(h, (list, tuple)) else [h]
            return [np.asarray(o) for o in hs]

        return ModelReplica(dispatch, harvest, device=desc,
                            on_device_topn=bool(top_n), pads_input=True)

    @classmethod
    def load_onnx(cls, path: str, int8: bool = False,
                  calibration_inputs=None, **kw) -> "InferenceModel":
        """Serve an .onnx file (onnx/loader.py).  ``int8=True`` runs
        post-training quantization: Gemm/MatMul nodes execute as int8
        MXU matmuls (ops/quantization.py) — with ``calibration_inputs``
        the activation scales are static (calibrated), otherwise dynamic.
        Replaces the reference's OpenVINO int8 path
        (InferenceModel.scala:443)."""
        from analytics_zoo_tpu.onnx import load_onnx

        program = load_onnx(path)
        if int8:
            from analytics_zoo_tpu.ops.quantization import quantize_program

            program = quantize_program(program, calibration_inputs)

        @jax.jit
        def fwd(*xs):
            out, _ = program.call(program.params, program.state, *xs,
                                  training=False)
            return out

        def forward(inputs: List[np.ndarray]):
            return fwd(*[jnp.asarray(x) for x in inputs])

        m = cls(forward, **kw)
        m._program, m._int8 = program, int8
        return m

    @classmethod
    def from_function(cls, fn: Callable, jit: bool = True,
                      **kw) -> "InferenceModel":
        """Serve an arbitrary jax function of the inputs."""
        jfn = jax.jit(fn) if jit else fn

        def forward(inputs: List[np.ndarray]):
            return jfn(*[jnp.asarray(x) for x in inputs])

        return cls(forward, **kw)

    @classmethod
    def load_tf_saved_model(cls, path: str, signature: str =
                            "serving_default", **kw) -> "InferenceModel":
        """Ingest a TF SavedModel via jax2tf.call_tf (reference
        doLoadTF/TFNet.fromSavedModel, TFNet.scala:654).  The TF graph
        executes on the host; JAX owns the calling side."""
        import tensorflow as tf  # gated: raises if TF absent
        from jax.experimental import jax2tf

        loaded = tf.saved_model.load(path)
        f = loaded.signatures[signature]
        call = jax2tf.call_tf(f)

        def forward(inputs: List[np.ndarray]):
            out = call(*[jnp.asarray(x) for x in inputs])
            if isinstance(out, dict):  # signature outputs are dicts
                vals = list(out.values())
                return vals[0] if len(vals) == 1 else vals
            return out

        m = cls(forward, **kw)
        m._tf_model = loaded  # keep alive
        return m

    @classmethod
    def load_tf_keras(cls, model_or_path, **kw) -> "InferenceModel":
        """Ingest a tf.keras model (object or .keras/.h5 path) —
        reference KerasModel serving (tfpark/model.py:34)."""
        import tensorflow as tf
        from jax.experimental import jax2tf

        model = (model_or_path if not isinstance(model_or_path, str)
                 else tf.keras.models.load_model(model_or_path))
        fn = tf.function(lambda *xs: model(*xs, training=False),
                         autograph=False)
        call = jax2tf.call_tf(fn)

        def forward(inputs: List[np.ndarray]):
            return call(*[jnp.asarray(x) for x in inputs])

        m = cls(forward, **kw)
        m._tf_model = model
        return m

    @classmethod
    def load_torch(cls, model_or_path, **kw) -> "InferenceModel":
        """Ingest a TorchScript file or torch.nn.Module (reference
        TorchNet.scala:39 — libtorch ran in-process via JNI; here torch
        runs in-process on the host CPU)."""
        import torch

        model = (torch.jit.load(model_or_path)
                 if isinstance(model_or_path, str) else model_or_path)
        model.eval()

        def forward(inputs: List[np.ndarray]):
            with torch.no_grad():
                out = model(*[torch.from_numpy(np.asarray(x))
                              for x in inputs])
            if isinstance(out, (tuple, list)):
                return [o.numpy() for o in out]
            return out.numpy()

        m = cls(forward, **kw)
        m._torch_model = model
        return m

    # -- predict -----------------------------------------------------------
    def predict(self, inputs, batch_size: Optional[int] = None):
        """Predict on one batch (list of arrays or a single array).

        Rows are padded up to the next batch bucket so repeated calls with
        ragged sizes reuse a bounded set of compiled programs (the
        reference bounded concurrency with a model-clone pool instead —
        InferenceModel.scala:67).  ``batch_size`` caps the per-program
        device batch (overrides the bucket for this call).
        """
        xs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        xs = [np.asarray(x) for x in xs]
        n = xs[0].shape[0]
        bucket = _next_bucket(n, self.batch_buckets)
        if batch_size:
            # snap an explicit cap DOWN to the nearest bucket: a cap
            # between buckets (say 40 with buckets (8, 64)) would
            # otherwise compile a fresh one-off 40-row program per novel
            # cap — chunking into full-bucket programs keeps the compiled
            # set bounded.  A cap below the smallest bucket is honored
            # as-is (the caller explicitly chose that program shape).
            eff = max((b for b in self.batch_buckets if b <= batch_size),
                      default=batch_size)
            bucket = min(eff, bucket)
        if bucket > n:
            xs = [np.concatenate(
                [x, np.repeat(x[-1:], bucket - n, axis=0)], axis=0)
                for x in xs]
        elif bucket < n:  # larger than biggest bucket (or capped): chunk
            eff = (tuple(b for b in self.batch_buckets if b <= bucket)
                   or (bucket,))
            outs, s = [], 0
            for m, b in plan_buckets(n, eff):
                outs.append(self.predict([x[s:s + m] for x in xs],
                                         batch_size=b))
                s += m
            if isinstance(outs[0], list):
                return [np.concatenate([o[i] for o in outs], axis=0)
                        for i in range(len(outs[0]))]
            return np.concatenate(outs, axis=0)
        self._note_shapes(xs)
        if self._cache is not None and self._net is not None:
            out = self._aot_forward(xs)
        else:
            out = self._forward(xs)
        if isinstance(out, (list, tuple)):
            return [np.asarray(o)[:n] for o in out]
        return np.asarray(out)[:n]

    def _aot_forward(self, xs):
        """Cache-backed predict() forward: same program as the closure-
        jitted ``_forward`` but param-explicit, so it routes through the
        persistent AOT table (warm shapes execute with zero live
        compiles)."""
        if self._pred_weights is None:
            w = self._qparams if self._int8 else self._params
            self._pred_weights = (w, self._state)
        p, s = self._pred_weights
        xj = [jnp.asarray(x) for x in xs]
        prog = self._aot_program(p, s, xj, device=None, top_n=None)
        return prog(p, s, *xj)

    # reference predict-API aliases (InferenceModel.scala:762-830)
    do_predict = predict

    def predict_classes(self, inputs, **kw) -> np.ndarray:
        out = self.predict(inputs, **kw)
        if isinstance(out, list):
            out = out[0]
        return np.argmax(out, axis=-1)


# ---------------------------------------------------------------------------
# Dynamic batching — the TPU replacement for the model-clone queue
# ---------------------------------------------------------------------------

class BatchRequest:
    """One queued request inside the DynamicBatcher: ``xs`` keep their
    leading batch dim (``n`` rows); ``callback(out, error)`` fires with
    the request's slice of the fused output (or the batch error).
    ``deadline`` (monotonic seconds, optional) is the record's client
    TTL: a request still unflushed past it is shed with a typed
    ``DeadlineExpired`` instead of wasting a device slot.  ``span``
    (optional observe.Span) is the record's batch_wait leg — the
    batcher ends it when the request flushes, sheds, or the batcher
    closes, so the request's timeline never dangles.  ``model`` names
    the target model in a multi-model pipeline (None = single-model
    legacy path); it rides into the bucket key so two models' requests
    never fuse, and into every per-request metric as a label."""

    __slots__ = ("xs", "n", "callback", "t_submit", "deadline", "span",
                 "model")

    def __init__(self, xs, callback, deadline=None, span=None, model=None):
        self.xs = xs
        self.n = xs[0].shape[0]
        self.callback = callback
        self.t_submit = time.monotonic()
        self.deadline = deadline
        self.span = span
        self.model = model


def scatter_batch_results(out, reqs: List[BatchRequest]) -> None:
    """Slice one fused model output back to the requests that formed it."""
    outs = out if isinstance(out, list) else [out]
    s = 0
    for r in reqs:
        sliced = [np.asarray(o)[s:s + r.n] for o in outs]
        r.callback(sliced if isinstance(out, list) else sliced[0], None)
        s += r.n


class DynamicBatcher:
    """Shape-bucketed continuous batching: stage 2 of the serving pipeline.

    Reference InferenceModel served N threads with N model clones
    (InferenceModel.scala:30-72); on TPU one compiled program is already
    thread-safe, so the win is *coalescing* small requests into one MXU
    batch.  Requests group by row shape/dtype (mixed-shape traffic never
    fuses — each shape is its own bucket feeding its own compiled
    program) and a bucket dispatches on whichever comes first:

    - **batch-full** — ``max_batch`` rows accumulated (preempts the
      deadline: a hot bucket never waits);
    - **deadline** — ``max_latency_ms`` since the bucket's oldest
      request (trickle traffic is never stranded).

    Two front doors: blocking ``predict`` (drop-in concurrency helper)
    and async ``submit(xs, callback)`` (the serving pipeline's path).
    ``dispatch_fn(key, fused, reqs)`` hands full batches to an external
    executor (the serving DeviceExecutor); without one, batches run
    inline through ``model.predict``.
    """

    def __init__(self, model: Optional[InferenceModel] = None,
                 max_batch: int = 64, max_latency_ms: float = 5.0,
                 dispatch_fn: Optional[Callable] = None,
                 name: str = "serving",
                 heartbeat: Optional[Callable[[], None]] = None):
        if model is None and dispatch_fn is None:
            raise ValueError("DynamicBatcher needs a model or a "
                             "dispatch_fn")
        self.model = model
        self.max_batch = max_batch
        self.max_latency = max_latency_ms / 1e3
        self.name = name
        self._dispatch_fn = dispatch_fn
        self._heartbeat = heartbeat
        self._cv = threading.Condition()
        self._buckets: Dict[Any, List[BatchRequest]] = {}
        self._rows: Dict[Any, int] = {}
        self._deadline: Dict[Any, float] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    @staticmethod
    def _key(xs) -> Any:
        return tuple((tuple(x.shape[1:]), str(x.dtype)) for x in xs)

    # -- front doors -------------------------------------------------------
    def submit(self, inputs, callback: Callable,
               deadline: Optional[float] = None, span=None,
               model: Optional[str] = None) -> None:
        """Async enqueue; ``callback(out, error)`` fires from the
        dispatch side when this request's slice is ready.  ``deadline``
        (monotonic) sheds the request with ``DeadlineExpired`` if it is
        still queued when the bucket flushes past it.  ``span`` is the
        caller's batch_wait span, ended by the batcher at flush/shed.
        ``model`` scopes the bucket: requests for different models never
        fuse into one device batch."""
        if self._stop.is_set():
            raise RuntimeError("DynamicBatcher is closed")
        xs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        xs = [np.asarray(x) for x in xs]
        req = BatchRequest(xs, callback, deadline=deadline, span=span,
                           model=model)
        key = self._key(xs) if model is None else (model,) + self._key(xs)
        full_reqs = None
        with self._cv:
            self._buckets.setdefault(key, []).append(req)
            self._rows[key] = self._rows.get(key, 0) + req.n
            self._deadline.setdefault(key, req.t_submit + self.max_latency)
            if self._rows[key] >= self.max_batch:
                # batch-full preempts the dispatcher thread: flush from
                # the submitting thread NOW rather than after the loop's
                # next GIL slot, so the device starts on batch N while
                # later requests are still being decoded/submitted
                full_reqs = self._buckets.pop(key)
                self._rows.pop(key, None)
                self._deadline.pop(key, None)
            self._cv.notify_all()
        if full_reqs is None:
            return
        groups, leftover = self._take(full_reqs, False)
        if leftover:
            with self._cv:
                self._buckets.setdefault(key, [])[:0] = leftover
                self._rows[key] = self._rows.get(key, 0) + sum(
                    r.n for r in leftover)
                self._deadline[key] = min(
                    self._deadline.get(key, float("inf")),
                    leftover[0].t_submit + self.max_latency)
        for g, full in groups:
            self._flush(key, g, full)

    def predict(self, inputs) -> Any:
        """Enqueue one request (single example or small batch); blocks
        until its slice of the fused batch returns."""
        done = threading.Event()
        slot: Dict[str, Any] = {}

        def cb(out, err):
            if err is not None:
                slot["error"] = err
            else:
                slot["out"] = out
            done.set()

        self.submit(inputs, cb)
        while not done.wait(timeout=1.0):
            if self._stop.is_set() and not done.is_set():
                # raced with close(): the worker may have exited before
                # popping this request — close() drains, but don't hang
                raise RuntimeError("DynamicBatcher closed while waiting")
        if "error" in slot:
            raise slot["error"]
        return slot["out"]

    def close(self, flush: bool = False):
        """Stop the dispatcher.  ``flush=True`` dispatches whatever is
        buffered first (graceful pipeline drain); pending requests left
        after that fail with RuntimeError so no caller blocks forever."""
        if flush and not self._stop.is_set():
            with self._cv:
                groups = [(k, self._buckets.pop(k))
                          for k in list(self._buckets)]
                self._rows.clear()
                self._deadline.clear()
            for key, reqs in groups:
                self._flush(key, reqs, full=False)
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        self._thread.join(timeout=2)
        with self._cv:
            pending = [r for reqs in self._buckets.values() for r in reqs]
            self._buckets.clear()
            self._rows.clear()
            self._deadline.clear()
        for r in pending:
            if r.span is not None:
                r.span.end(status="closed")
            r.callback(None, RuntimeError("DynamicBatcher closed"))

    # -- dispatcher --------------------------------------------------------
    def _ready(self, now: float) -> List[Any]:
        full = [k for k, r in self._rows.items() if r >= self.max_batch]
        due = [k for k, d in self._deadline.items()
               if k not in full and d <= now and self._rows.get(k)]
        return full + due

    def _loop(self):
        while not self._stop.is_set():
            if self._heartbeat is not None:
                self._heartbeat()
            flushes = []
            with self._cv:
                now = time.monotonic()
                ready = self._ready(now)
                if not ready:
                    timeout = 0.05
                    if self._deadline:
                        timeout = min(timeout, max(
                            1e-4, min(self._deadline.values()) - now))
                    self._cv.wait(timeout=timeout)
                    now = time.monotonic()
                    ready = self._ready(now)
                for key in ready:
                    reqs = self._buckets.pop(key, [])
                    self._rows.pop(key, None)
                    deadline_hit = self._deadline.pop(key, now) <= now
                    if not reqs:
                        continue
                    groups, leftover = self._take(reqs, deadline_hit)
                    flushes.extend((key, g, f) for g, f in groups)
                    if leftover:
                        # a full-flush leaves the partial tail batching
                        # toward its own (original-arrival) deadline
                        self._buckets[key] = leftover
                        self._rows[key] = sum(r.n for r in leftover)
                        self._deadline[key] = (leftover[0].t_submit
                                               + self.max_latency)
            for key, reqs, full in flushes:
                self._flush(key, reqs, full)

    def _take(self, reqs, deadline_hit):
        """Pack requests into ≤max_batch-row groups (request boundaries
        respected; a single oversized request flushes alone)."""
        groups, cur, rows = [], [], 0
        for r in reqs:
            if cur and rows + r.n > self.max_batch:
                groups.append((cur, True))
                cur, rows = [], 0
            cur.append(r)
            rows += r.n
        leftover = []
        if cur:
            if rows >= self.max_batch or deadline_hit:
                groups.append((cur, rows >= self.max_batch))
            else:
                leftover = cur
        return groups, leftover

    def _flush(self, key, reqs: List[BatchRequest], full: bool) -> None:
        from analytics_zoo_tpu.core.profiling import TIMERS
        from analytics_zoo_tpu.observe import metrics as obs
        from analytics_zoo_tpu.robust.errors import DeadlineExpired

        now = time.monotonic()
        expired = [r for r in reqs
                   if r.deadline is not None and now > r.deadline]
        if expired:
            # shed before paying the dispatch: the client's TTL already
            # elapsed while the request batched, so answer the typed
            # error now and keep the device slot for live work
            for r in expired:
                obs.count("serving_shed_total", code="expired",
                          model=r.model or DEFAULT_MODEL,
                          flat=f"{self.name}/shed_expired")
            err = DeadlineExpired(
                "client TTL expired while the request batched")
            for r in expired:
                if r.span is not None:
                    r.span.end(status="expired")
                r.callback(None, err)
            reqs = [r for r in reqs if r not in expired]
            if not reqs:
                return
        TIMERS.incr(f"{self.name}/flush_full" if full
                    else f"{self.name}/flush_deadline")
        for r in reqs:
            obs.observe("serving_stage_seconds", now - r.t_submit,
                        stage="batch_wait", model=r.model or DEFAULT_MODEL,
                        flat=f"{self.name}/batch_wait")
            if r.span is not None:
                r.span.end(rows=r.n, full=full)
        try:
            if len(reqs) == 1:
                # single-request batch: hand the arrays through as-is —
                # on the shm backend these are views into the slot, and
                # this is the last place a host copy could sneak in
                # before device_put
                fused = list(reqs[0].xs)
            else:
                TIMERS.incr(f"{self.name}/batch_fuse_copies")
                fused = [np.concatenate([r.xs[i] for r in reqs], axis=0)
                         for i in range(len(reqs[0].xs))]
            if self._dispatch_fn is not None:
                self._dispatch_fn(key, fused, reqs)
                return
            out = self.model.predict(fused)
            scatter_batch_results(out, reqs)
        except Exception as e:  # surface errors to every waiter
            for r in reqs:
                r.callback(None, e)
