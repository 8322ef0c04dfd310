"""Chip smoke: the quickest proof that the system still starts on the TPU.

One process, no children, no network.  Drives the main paths once through
the entry points users call — ``Estimator.fit`` (NeuralCF at MovieLens-1M
width, ResNet-50 at 224x224), ``ClusterServing`` (MobileNet behind the queue
path) and every ``ops/`` kernel auto-dispatch selects on TPU — on synthetic
data made from a seed, checks each result by the repo's own means, and
prints as the LAST line of stdout::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

It exits non-zero, names the phase and prints no result line when jax finds
no TPU or when any phase fails.  With four or more devices it also runs the
multi-chip phase (dp/tp/sp/pp/ep through ``fit``, a sharded-table lookup and
ring attention with the Pallas hop kernel); on one device that phase prints
"skipped: 1 device".  Phase times are set-up evidence, not benchmark metrics.

    python chip_smoke.py            # on the chip machine, from the repo root
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time
from typing import Any, Callable, Dict

import numpy as np

# Published widths (depth and run length cut, never width).  The tests run
# the same phase functions with their own tiny sizes.
FULL: Dict[str, Any] = {
    "train/ncf": dict(user_count=6040, item_count=3706, ratings_per_user=165,
                      batch=8192, steps_per_execution=8, held_out=65536),
    "train/resnet50": dict(net=None, image=224, classes=1000, batch=256,
                           steps=4),
    "serve": dict(net=None, image=224, classes=1000, buckets=(1, 32),
                  burst=64),
    "kernels": dict(
        flash=dict(B=4, H=8, L=2048, D=128),
        # granite-4.0-h-micro's mixer on 2 x 4,096 tokens
        scan=dict(B=2, L=4096, H=64, P=64, G=1, N=128, chunk=256),
        # shape classes the bundled models send (auto-dispatch must keep
        # them on the reference path) and the one the kernel is for
        bag_model=[dict(name="ncf_user", V=6041, D=20, B=8192, N=1),
                   dict(name="wnd_deep_embed", V=5001, D=64, B=8192, N=1),
                   dict(name="wnd_wide", V=12000, D=2, B=8192, N=4)],
        bag_kernel=dict(name="dlrm_like", V=1 << 20, D=128, B=1024, N=32),
        dequant=dict(K=4096, N=4096, Ms=(32, 256)),
        interpret=False),
    "multichip": dict(dryrun=True, bag=dict(V=1 << 16, D=128, B=1024, N=8),
                      ring=dict(B=1, H=4, L=8192, D=128), interpret=False),
}


class PhaseFailed(RuntimeError):
    """A check inside a phase did not hold."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def _timed(fn: Callable[[], Any]):
    """(result, seconds) with the result drained on the device."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _first_and_steady(fn: Callable[[], Any], repeats: int = 3):
    """Result, first-call seconds (trace + compile + run) and the best of
    ``repeats`` later calls."""
    out, first = _timed(fn)
    steady = min(_timed(fn)[1] for _ in range(repeats))
    return out, first, steady


def _rel_err(got, want) -> float:
    """max|got - want| over max|want|, in float32 on the host."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def _selected(mark) -> Dict[str, float]:
    """``ops_kernel_selected_total`` series counted since ``mark``."""
    from analytics_zoo_tpu.observe.metrics import METRICS

    return {k: v for k, v in METRICS.delta(mark)["counters"].items()
            if k.startswith("ops_kernel_selected_total")}


def _series(kernel: str, path: str) -> str:
    from analytics_zoo_tpu.observe.metrics import render_series

    return render_series("ops_kernel_selected_total",
                         (("kernel", kernel), ("path", path)))


def _cache_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


# ---------------------------------------------------------------------------
# train/ncf
# ---------------------------------------------------------------------------

def phase_train_ncf(size: Dict[str, Any]) -> Dict[str, Any]:
    """NeuralCF at MovieLens-1M width through compile/fit/evaluate/
    recommend_for_user, bf16 compute, fused steps."""
    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.data.datasets import generate_movielens_like
    from analytics_zoo_tpu.models import NeuralCF
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.observe.metrics import METRICS
    from analytics_zoo_tpu.parallel.sharding import device_span
    from analytics_zoo_tpu.train.optimizers import Adam

    init_zoo_context(compute_dtype="bfloat16",
                     steps_per_execution=size["steps_per_execution"])
    reset_name_scope()
    users, items, ratings = generate_movielens_like(
        n_users=size["user_count"], n_items=size["item_count"],
        ratings_per_user=size["ratings_per_user"], seed=0)
    perm = np.random.RandomState(0).permutation(len(users))
    x = [users[perm, None].astype(np.int32),
         items[perm, None].astype(np.int32)]
    y = (ratings[perm] - 1).astype(np.int32)
    held = size["held_out"]
    x_tr, y_tr = [a[held:] for a in x], y[held:]
    x_ho, y_ho = [a[:held] for a in x], y[:held]

    mark = METRICS.snapshot()
    ncf = NeuralCF(user_count=size["user_count"],
                   item_count=size["item_count"], class_num=5,
                   user_embed=20, item_embed=20, hidden_layers=(40, 20, 10),
                   mf_embed=20)
    ncf.compile(optimizer=Adam(lr=1e-3),
                loss="sparse_categorical_crossentropy", metrics=["accuracy"])
    before = ncf.evaluate(x_ho, y_ho, batch_size=size["batch"])
    t0 = time.perf_counter()
    ncf.fit(x_tr, y_tr, batch_size=size["batch"], nb_epoch=1, verbose=False)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()     # nb_epoch is the epoch to stop at
    ncf.fit(x_tr, y_tr, batch_size=size["batch"], nb_epoch=2, verbose=False)
    steady = time.perf_counter() - t0
    after = ncf.evaluate(x_ho, y_ho, batch_size=size["batch"])
    recs = ncf.recommend_for_user(
        1, np.arange(1, min(size["item_count"], 500) + 1), max_items=5)

    est = ncf.estimator
    selected = _selected(mark)
    print(f"  rows={len(y_tr)} batch={size['batch']} "
          f"data_path={est.last_data_path} ({est.last_data_path_reason})")
    print(f"  held-out loss {before['loss']:.4f} -> {after['loss']:.4f}, "
          f"accuracy {after['accuracy']:.3f}; recommend_for_user(1): {recs}")
    for series, n in sorted(selected.items()):
        print(f"  {series} {n:g}")
    print(f"  epoch wall: first (with compile) {first:.2f}s, "
          f"steady {steady:.2f}s")
    _check(np.isfinite(before["loss"]) and np.isfinite(after["loss"]),
           f"non-finite loss {before} -> {after}")
    _check(after["loss"] < before["loss"],
           f"loss did not fall: {before['loss']} -> {after['loss']}")
    _check(len(recs) == 5 and all(np.isfinite(s) for _, s in recs),
           f"bad recommendations {recs}")
    _check(any("embedding_gather" in s for s in selected),
           f"the user/item tables never reached the lookup dispatch: "
           f"{selected}")
    span = device_span(est.params)
    _check(span == len(jax.devices()),
           f"params span {span} device(s) of {len(jax.devices())}")
    return {"param_span": span, "selected": selected}


# ---------------------------------------------------------------------------
# train/resnet50
# ---------------------------------------------------------------------------

def phase_train_resnet50(size: Dict[str, Any]) -> None:
    """ResNet-50 at 224x224x3, bf16, batch 256: four optimizer steps
    through compile/fit."""
    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.models.image.imageclassification import resnet50
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.train.optimizers import SGD

    init_zoo_context(compute_dtype="bfloat16")
    reset_name_scope()
    hw, classes = size["image"], size["classes"]
    net = (size["net"] or resnet50)(class_num=classes,
                                    input_shape=(hw, hw, 3))
    n = size["batch"] * size["steps"]
    rng = np.random.default_rng(1)
    x = rng.standard_normal((n, hw, hw, 3), dtype=np.float32)
    y = rng.integers(0, classes, n).astype(np.int32)
    net.compile(optimizer=SGD(lr=0.01, momentum=0.9),
                loss="sparse_categorical_crossentropy_with_logits")
    t0 = time.perf_counter()
    losses = [net.fit(x, y, batch_size=size["batch"], nb_epoch=1,
                      verbose=False)[-1]["loss"]]
    first = time.perf_counter() - t0
    t0 = time.perf_counter()     # nb_epoch is the epoch to stop at
    losses.append(net.fit(x, y, batch_size=size["batch"], nb_epoch=2,
                          verbose=False)[-1]["loss"])
    steady = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  {size['steps']} steps x batch {size['batch']} at {hw}x{hw}x3, "
          f"epoch losses {losses[0]:.4f}, {losses[1]:.4f}")
    print(f"  peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}")
    print(f"  {size['steps']}-step wall: first (with compile) {first:.2f}s, "
          f"steady {steady:.2f}s")
    _check(all(np.isfinite(v) for v in losses), f"non-finite loss {losses}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(size: Dict[str, Any]) -> None:
    """MobileNet behind the full queue path: enqueue -> poller -> decode ->
    batcher -> DeviceExecutor -> respond, uint8 wire format."""
    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.deploy import (ClusterServing, InferenceModel,
                                          ServingConfig, imagenet_preprocess)
    from analytics_zoo_tpu.deploy.serving import (InputQueue, MemoryQueue,
                                                  OutputQueue)
    from analytics_zoo_tpu.models.image.imageclassification import mobilenet
    from analytics_zoo_tpu.nn import reset_name_scope

    init_zoo_context()
    reset_name_scope()
    hw, classes, burst = size["image"], size["classes"], size["burst"]
    net = (size["net"] or mobilenet)(class_num=classes,
                                     input_shape=(hw, hw, 3))
    params, state = net.init(jax.random.PRNGKey(0))
    model = InferenceModel.from_keras_net(
        net, params, state, preprocess=imagenet_preprocess(),
        batch_buckets=tuple(size["buckets"]))
    rs = np.random.RandomState(2)
    images = rs.randint(0, 256, (2 * (burst + 1), hw, hw, 3)).astype(np.uint8)

    q = MemoryQueue()
    srv = ClusterServing(model, q, ServingConfig(
        batch_size=max(size["buckets"]), poll_timeout_s=0.01,
        max_batch_delay_ms=5.0, decode_workers=4, max_inflight=2)).start()
    inp, outp = InputQueue(q), OutputQueue(q)

    def round_trip(tag: str, imgs) -> float:
        t0 = time.perf_counter()
        for i, img in enumerate(imgs):
            inp.enqueue(uri=f"{tag}{i}", x=img)
        for i in range(len(imgs)):
            res = outp.query(f"{tag}{i}", timeout=900.0)
            _check(isinstance(res, np.ndarray) and res.shape == (classes,)
                   and bool(np.all(np.isfinite(res))),
                   f"request {tag}{i}: expected a finite ({classes},) "
                   f"result, got {type(res).__name__} "
                   f"{getattr(res, 'shape', res)}")
        return time.perf_counter() - t0

    try:
        single_first = round_trip("single_a", images[:1])
        burst_first = round_trip("burst_a", images[1:1 + burst])
        single = round_trip("single_b", images[1 + burst:2 + burst])
        burst_s = round_trip("burst_b", images[2 + burst:])
        health = srv.health()
    finally:
        srv.stop()

    counters = health["counters"]
    device_batches = sum(v for k, v in counters.items()
                         if k.endswith("/device_batches"))
    fallback = sum(v for k, v in counters.items()
                   if k.endswith("/sync_fallback_batches"))
    states = health["replica_states"]
    known = {str(d) for d in jax.devices()}
    print(f"  answered {2 * (burst + 1)} requests; device_batches="
          f"{device_batches:g} sync_fallback_batches={fallback:g} "
          f"replicas_healthy={health['replicas_healthy']}/"
          f"{health['replicas']}")
    print(f"  replicas: {[(s['device'], s['health']) for s in states]}")
    print(f"  single request: first (with compile) {single_first:.2f}s, "
          f"steady {single * 1e3:.1f}ms; burst of {burst}: first "
          f"{burst_first:.2f}s, steady {burst_s * 1e3:.1f}ms")
    _check(device_batches > 0, "no batch reached a device replica")
    _check(fallback == 0, f"{fallback:g} batch(es) were answered by the "
           "synchronous fallback: every replica was quarantined")
    _check(states and all(s["health"] != "quarantined" for s in states),
           f"quarantined replica: {states}")
    _check(all(s["device"] in known for s in states),
           f"replica device not one of {sorted(known)}: {states}")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _flash_check(size: Dict[str, int], interpret: bool) -> None:
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.observe.metrics import METRICS
    from analytics_zoo_tpu.ops.attention import (dot_product_attention,
                                                 reference_attention)
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    B, H, L, D = size["B"], size["H"], size["L"], size["D"]
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (B, H, L, D), jnp.bfloat16)
               for kk in ks)

    def kernel(q, k, v):
        if interpret:
            return flash_attention(q, k, v, True, None, 256, 256, True)
        return dot_product_attention(q, k, v, causal=True)

    def ref(q, k, v):
        return reference_attention(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32), causal=True)

    def grads(f):
        return jax.jit(jax.grad(
            lambda q, k, v: f(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    mark = METRICS.snapshot()
    out, f_first, f_steady = _first_and_steady(
        functools.partial(jax.jit(kernel), q, k, v))
    g, g_first, g_steady = _first_and_steady(
        functools.partial(grads(kernel), q, k, v))
    selected = _selected(mark)
    want, _, r_steady = _first_and_steady(
        functools.partial(jax.jit(ref), q, k, v))
    gwant, _, rg_steady = _first_and_steady(
        functools.partial(grads(ref), q, k, v))
    err = _rel_err(out, want)
    gerr = max(_rel_err(a, b) for a, b in zip(g, gwant))
    print(f"  flash_attention B{B} H{H} L{L} D{D} bf16 causal: fwd "
          f"{f_steady * 1e3:.2f}ms (reference {r_steady * 1e3:.2f}ms), "
          f"fwd+bwd {g_steady * 1e3:.2f}ms (reference "
          f"{rg_steady * 1e3:.2f}ms); first call {f_first:.2f}s / "
          f"{g_first:.2f}s; rel err fwd {err:.2e} bwd {gerr:.2e}")
    _check(err < 3e-2 and gerr < 6e-2,
           f"flash_attention disagrees with reference_attention: fwd "
           f"{err:.3e}, bwd {gerr:.3e}")
    if not interpret:
        _check(_series("flash_attention", "pallas") in selected,
               f"auto-dispatch did not take the Pallas kernel: {selected}")


def _scan_check(size: Dict[str, int], interpret: bool) -> None:
    """The Mamba-2 scan's kernels against ``ssd_chunked``, output and the
    five gradients, through the mixer's own dispatch."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.nn.layers.ssm import Mamba2Mixer, ssd_chunked
    from analytics_zoo_tpu.observe.metrics import METRICS
    from analytics_zoo_tpu.ops.ssm_scan import ssm_scan

    B, L, H, P, G, N = (size[k] for k in "BLHPGN")
    chunk = size["chunk"]
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, L, H, P), jnp.bfloat16)
    b, c = (jax.random.normal(k, (B, L, G, N), jnp.bfloat16)
            for k in ks[1:3])
    # the Mamba-2 defaults: delta log-uniform in 1e-3..1e-1, A in 1..16
    dt = jnp.exp(jax.random.uniform(ks[3], (B, L, H), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    a = -jax.random.uniform(ks[4], (H,), jnp.float32, 1.0, 16.0)
    args = (x, dt, a, b, c)

    def kernel(*args):
        return ssm_scan(*args, chunk, interpret)

    def ref(*args):
        return ssd_chunked(*args, chunk)

    def grads(f):
        return jax.jit(jax.grad(lambda *args: f(*args).sum(),
                                argnums=range(5)))

    out, f_first, f_steady = _first_and_steady(
        functools.partial(jax.jit(kernel), *args))
    g, g_first, g_steady = _first_and_steady(
        functools.partial(grads(kernel), *args))
    want, _, r_steady = _first_and_steady(
        functools.partial(jax.jit(ref), *args))
    gwant, _, rg_steady = _first_and_steady(
        functools.partial(grads(ref), *args))
    err = _rel_err(out, want)
    gerrs = {n: _rel_err(u, v) for n, u, v in zip(
        ("x", "dt", "a", "b", "c"), g, gwant)}
    print(f"  ssm_scan B{B} L{L} H{H} P{P} G{G} N{N} chunk {chunk} bf16: "
          f"fwd {f_steady * 1e3:.2f}ms (reference {r_steady * 1e3:.2f}ms), "
          f"fwd+bwd {g_steady * 1e3:.2f}ms (reference "
          f"{rg_steady * 1e3:.2f}ms); first call {f_first:.2f}s / "
          f"{g_first:.2f}s; rel err fwd {err:.2e} bwd "
          + " ".join(f"{n} {e:.2e}" for n, e in gerrs.items()))
    _check(err < 3e-2 and max(gerrs.values()) < 6e-2,
           f"ssm_scan disagrees with ssd_chunked: fwd {err:.3e}, bwd "
           f"{gerrs}")
    if not interpret:
        # what the mixer's dispatch selects at this shape
        mixer = Mamba2Mixer(H * P // 2, n_heads=H, head_dim=P, d_state=N,
                            n_groups=G, chunk_size=chunk,
                            name="chip_smoke_mixer")
        mark = METRICS.snapshot()
        jax.eval_shape(
            lambda xbc, dt: mixer._scan(
                {"dt_bias": jnp.zeros((H,)), "A_log": jnp.zeros((H,)),
                 "D": jnp.ones((H,))}, xbc, dt),
            jax.ShapeDtypeStruct((B, L, mixer.conv_dim), jnp.bfloat16),
            jax.ShapeDtypeStruct((B, L, H), jnp.bfloat16))
        selected = _selected(mark)
        _check(_series("ssm_scan", "pallas") in selected,
               f"the mixer did not take the Pallas kernels: {selected}")


def _bag_inputs(shape: Dict[str, Any], pad_id):
    import jax
    import jax.numpy as jnp

    kt, ki = jax.random.split(jax.random.PRNGKey(4))
    table = jax.random.normal(kt, (shape["V"], shape["D"]), jnp.float32)
    ids = jax.random.randint(ki, (shape["B"], shape["N"]), 0, shape["V"],
                             jnp.int32)
    if pad_id is not None:      # a ragged tail, as padded bags have
        ids = ids.at[:, shape["N"] // 2:].set(
            jnp.where(ids[:, shape["N"] // 2:] % 3 == 0, pad_id,
                      ids[:, shape["N"] // 2:]))
    return table, ids


def _bag_check(shape: Dict[str, Any], interpret: bool,
               expect_kernel: bool) -> None:
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.observe.metrics import METRICS
    from analytics_zoo_tpu.ops.embedding_bag import (embedding_bag,
                                                     embedding_bag_reference,
                                                     embedding_gather)

    gather = shape["N"] == 1
    kernel_name = "embedding_gather" if gather else "embedding_bag"
    pad_id = None if gather else 0
    table, ids = _bag_inputs(shape, pad_id)

    def kernel(t, i):
        if gather:
            return embedding_gather(t, i, interpret=interpret)[:, 0]
        return embedding_bag(t, i, "mean", pad_id, interpret=interpret)

    def ref(t, i):
        if gather:
            return jnp.take(t, i[:, 0], axis=0)
        return embedding_bag_reference(t, i, "mean", pad_id)

    def grads(f):
        return jax.jit(jax.grad(lambda t, i: (f(t, i) ** 2).sum()))

    path = ("interpret" if interpret
            else "pallas" if expect_kernel else "reference")
    what = (f"{kernel_name} {shape['name']} V{shape['V']} D{shape['D']} "
            f"ids({shape['B']},{shape['N']}) f32 -> path {path}")
    mark = METRICS.snapshot()
    out, f_first, f_steady = _first_and_steady(
        functools.partial(jax.jit(kernel), table, ids))
    g, g_first, g_steady = _first_and_steady(
        functools.partial(grads(kernel), table, ids))
    selected = _selected(mark)
    _check(_series(kernel_name, path) in selected,
           f"{what}: dispatch counted {selected}")
    if path == "reference":     # the public entry IS the reference here
        print(f"  {what}: fwd {f_steady * 1e3:.3f}ms, fwd+bwd "
              f"{g_steady * 1e3:.3f}ms; first call {f_first:.2f}s / "
              f"{g_first:.2f}s")
        return
    want, _, r_steady = _first_and_steady(
        functools.partial(jax.jit(ref), table, ids))
    gwant, _, rg_steady = _first_and_steady(
        functools.partial(grads(ref), table, ids))
    err, gerr = _rel_err(out, want), _rel_err(g, gwant)
    print(f"  {what}: fwd {f_steady * 1e3:.3f}ms (reference "
          f"{r_steady * 1e3:.3f}ms), fwd+bwd {g_steady * 1e3:.3f}ms "
          f"(reference {rg_steady * 1e3:.3f}ms); first call {f_first:.2f}s "
          f"/ {g_first:.2f}s; rel err fwd {err:.2e} bwd {gerr:.2e}")
    _check(err < 1e-5 and gerr < 1e-4,
           f"{what} disagrees with its reference: fwd {err:.3e}, bwd "
           f"{gerr:.3e}")


def _dequant_check(size: Dict[str, Any], interpret: bool) -> None:
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.observe.metrics import METRICS
    from analytics_zoo_tpu.ops.dequant_matmul import (
        dequant_matmul, dequant_matmul_reference, quantize_weights)

    K, N = size["K"], size["N"]
    kw, kx = jax.random.split(jax.random.PRNGKey(5))
    w = jax.random.normal(kw, (K, N), jnp.float32) / np.sqrt(K)
    for bits in (8, 4):
        qw, scale = quantize_weights(w, bits)
        for M in size["Ms"]:
            x = jax.random.normal(kx, (M, K), jnp.float32)
            mark = METRICS.snapshot()
            out, first, steady = _first_and_steady(functools.partial(
                jax.jit(lambda x, qw, scale, bits=bits: dequant_matmul(
                    x, qw, scale, bits, K, interpret=interpret)),
                x, qw, scale))
            selected = _selected(mark)
            with jax.default_matmul_precision("float32"):
                want, _, r_steady = _first_and_steady(functools.partial(
                    jax.jit(lambda x, qw, scale, bits=bits:
                            dequant_matmul_reference(x, qw, scale, bits, K)),
                    x, qw, scale))
            err = _rel_err(out, want)
            path = "interpret" if interpret else "pallas"
            print(f"  dequant_matmul int{bits} M{M} K{K} N{N} -> path "
                  f"{path}: {steady * 1e3:.3f}ms (reference, f32 "
                  f"precision, {r_steady * 1e3:.3f}ms); first call "
                  f"{first:.2f}s; rel err {err:.2e}")
            _check(err < 2e-2, f"dequant_matmul int{bits} M{M} disagrees "
                   f"with its reference: {err:.3e}")
            _check(_series("dequant_matmul", path) in selected,
                   f"dequant_matmul int{bits} M{M}: expected path {path}, "
                   f"dispatch counted {selected}")


def phase_kernels(size: Dict[str, Any]) -> None:
    """Every ops/ kernel on the path auto-dispatch takes on TPU (compiled
    by Mosaic, never interpreted), forward and backward, against its own
    pure-JAX reference; and the model shapes dispatch keeps off the
    kernel, with the reference's time for them."""
    from analytics_zoo_tpu import init_zoo_context

    init_zoo_context()
    interpret = size["interpret"]
    _flash_check(size["flash"], interpret)
    _scan_check(size["scan"], interpret)
    for shape in size["bag_model"]:
        # Mosaic refuses these rows (narrower than a 128-lane tile):
        # dispatch must keep them on the reference path
        _bag_check(shape, interpret=False, expect_kernel=False)
    _bag_check(size["bag_kernel"], interpret, expect_kernel=True)
    _bag_check(dict(size["bag_kernel"], name="gather", N=1,
                    B=size["bag_kernel"]["B"] * 8), interpret,
               expect_kernel=True)
    _dequant_check(size["dequant"], interpret)


# ---------------------------------------------------------------------------
# multi-chip
# ---------------------------------------------------------------------------

def phase_multichip(size: Dict[str, Any]) -> Dict[str, Any]:
    """dp/tp/sp/pp/ep through fit, one sharded-table lookup and one ring
    attention with the Pallas hop kernel inside shard_map, each asserted
    from ``.sharding`` to span every device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.observe.metrics import METRICS
    from analytics_zoo_tpu.ops.attention import reference_attention
    from analytics_zoo_tpu.ops.embedding_bag import embedding_bag_reference
    from analytics_zoo_tpu.ops.ring_attention import ring_attention
    from analytics_zoo_tpu.parallel.table_sharding import sharded_bag

    n = len(jax.devices())
    if n < 4:
        print(f"  skipped: {n} device")
        return {"skipped": True}

    if size["dryrun"]:
        import __graft_entry__

        __graft_entry__.dryrun_multichip(n)

    # one lookup against a table row-sharded over every device
    ctx = init_zoo_context(mesh_shape=(1, n), axis_names=("data", "model"))
    table, ids = _bag_inputs(dict(size["bag"]), None)
    table = jax.device_put(table, NamedSharding(ctx.mesh, P("model", None)))
    _check(len(table.sharding.device_set) == n
           and not table.is_fully_replicated,
           f"table not sharded over {n} devices: {table.sharding}")
    want = embedding_bag_reference(jax.device_get(table),
                                   jax.device_get(ids), "sum", None)
    # dedup=None is the default routing (unique-id path for sharded
    # lookups); dedup=False puts the fused bag kernel inside shard_map
    for dedup in (None, False):
        lookup = jax.jit(lambda t, i, dedup=dedup: sharded_bag(
            t, i, "sum", None, mesh=ctx.mesh, axis="model", dedup=dedup))
        mark = METRICS.snapshot()
        out, first, steady = _first_and_steady(
            functools.partial(lookup, table, ids))
        err = _rel_err(out, want)
        print(f"  sharded_bag V{size['bag']['V']} D{size['bag']['D']} over "
              f"{n} shards, dedup={dedup}: {steady * 1e3:.3f}ms, first "
              f"call {first:.2f}s, rel err {err:.2e}; {_selected(mark)}")
        _check(err < 1e-5,
               f"sharded_bag(dedup={dedup}) disagrees with the reference: "
               f"{err}")

    # ring attention: L sharded over every device, Pallas hops on TPU
    ctx = init_zoo_context(mesh_shape=(n,), axis_names=("seq",))
    r = size["ring"]
    seq = NamedSharding(ctx.mesh, P(None, None, "seq", None))
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.device_put(jax.random.normal(
        kk, (r["B"], r["H"], r["L"], r["D"]), jnp.bfloat16), seq)
        for kk in ks)
    force = "interpret" if size["interpret"] else None
    mark = METRICS.snapshot()
    ring = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=ctx.mesh, axis="seq", causal=True, force=force))
    out, first, steady = _first_and_steady(functools.partial(ring, q, k, v))
    selected = _selected(mark)
    want = reference_attention(*(a.astype(jnp.float32) for a in (q, k, v)),
                               causal=True)
    err = _rel_err(out, want)
    path = "interpret" if size["interpret"] else "pallas"
    print(f"  ring_attention B{r['B']} H{r['H']} L{r['L']} D{r['D']} bf16 "
          f"over {n} shards -> path {path}: {steady * 1e3:.2f}ms, first "
          f"call {first:.2f}s, rel err {err:.2e}; out sharding "
          f"{out.sharding.spec}")
    _check(err < 3e-2, f"ring_attention disagrees with reference: {err}")
    _check(_series("ring_attention", path) in selected,
           f"ring_attention: expected path {path}, counted {selected}")
    _check(len(out.sharding.device_set) == n
           and not out.is_fully_replicated,
           f"ring output not sharded over {n} devices: {out.sharding}")
    init_zoo_context()
    return {"devices": n}


PHASES = (("train/ncf", phase_train_ncf),
          ("train/resnet50", phase_train_resnet50),
          ("serve", phase_serve),
          ("kernels", phase_kernels),
          ("multichip", phase_multichip))


def main() -> int:
    import jax

    from analytics_zoo_tpu import native
    from analytics_zoo_tpu.core.context import (describe_devices,
                                                enable_compile_cache)

    cache_dir = enable_compile_cache()
    device = describe_devices()
    print(f"chip_smoke: platform={device['platform']} device_kind="
          f"{device['kind']} devices={device['count']} "
          f"jax={jax.__version__} native.available()={native.available()}")
    print(f"chip_smoke: compile cache {cache_dir} "
          f"({_cache_entries(cache_dir)} entries at start)")
    if device["platform"] != "tpu":
        print(f"chip_smoke: FAILED phase=device: jax found "
              f"{device['platform']}, not a TPU", file=sys.stderr)
        return 1
    t_all = time.perf_counter()
    for name, fn in PHASES:
        print(f"chip_smoke: phase {name}", flush=True)
        t0 = time.perf_counter()
        try:
            fn(FULL[name])
        except BaseException:
            print(f"chip_smoke: FAILED phase={name}", file=sys.stderr,
                  flush=True)
            raise
        print(f"chip_smoke: phase {name} ok in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(f"chip_smoke: all phases ok in {time.perf_counter() - t_all:.1f}s; "
          f"compile cache {cache_dir} ({_cache_entries(cache_dir)} entries "
          "at end)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
