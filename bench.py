"""Benchmark: the north star is NCF MovieLens-1M training throughput
(samples/sec/chip) *at matched accuracy* (BASELINE.json: >=10x CPU).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
The primary metric is NCF training throughput (bf16 compute); "extra"
carries the supporting evidence the north star asks for:

- ncf_hitrate_at_10: a real negative-sampled MovieLens-1M-shaped run
  through FeatureSet -> Estimator (prefetch + the full framework path),
  trained to convergence and evaluated with the NCF paper's protocol
  (held-out positive vs 99 negatives, HR@10).  The true MovieLens file
  is not fetchable here (zero egress); the generator reproduces its
  shape (6040x3706), sparsity (50 interactions/user - ml-1m's true mean
  is ~165), and a learnable latent-factor structure with a quoted
  oracle ceiling: HR@10 0.86 vs oracle 0.975, i.e. the framework
  recovers ~88%% of the recoverable signal.
- ncf_f32 / ncf_bf16: the mixed-precision delta (compute_dtype knob).
- featureset_data_paths: end-to-end samples/sec of BOTH Estimator data
  paths (host PrefetchIterator vs HBM-resident FeatureSet with
  on-device shuffle) on NCF- and WideAndDeep-shaped inputs, so the
  host-input gap closure is measured, not asserted.
- resnet50_ghostbn025_imgs_per_sec: BASELINE config #2 throughput
  (bf16 train step, ghost-BN stats_fraction=0.25; batch 256 by on-chip
  sweep - 1559 imgs/s vs 305 at batch 32, the MXU needs the batch to
  tile).  resnet50_imgs_per_sec_per_chip is the full-BN leg under the
  historical key, so cross-round comparisons stay variant-matched.
- resnet_accuracy: config #2's accuracy leg — cats-vs-dogs-shaped
  convergence with a quoted ceiling.
- wide_and_deep_samples_per_sec / nnframes: BASELINE configs #4 and #3,
  so all five configs carry measurements.
- attention_l{1024,2048,8192}: the hand-written Pallas kernel ON SILICON
  vs the pure-XLA blockwise fallback vs the STOCK pallas tpu kernel
  (adopt-or-beat).

Baseline: the same jitted training step on the host CPU — the honest
stand-in for "BigDL-on-CPU on this machine" given BigDL targets CPU and
publishes no absolute numbers (BASELINE.md).
"""

import contextlib as _contextlib
import json
import os
import time

import numpy as np

# Wall-clock budget: optional extras are skipped once exceeded so the
# primary metric always prints within the driver's window.
_T0 = time.time()
# A watchdog (below) prints the JSON line with whatever sections
# completed if the run outlives the budget, and exits non-zero.
_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "700"))


def _remaining() -> float:
    return _BUDGET_S - (time.time() - _T0)


# Typed skip reasons: every leg the runner elides goes through _skip()
# so the artifact's skip markers form a closed vocabulary that drift
# checks and dashboards can rely on (no free-form strings).
SKIP_TIME_BUDGET = "time budget"
SKIP_SHM = "POSIX shared memory unavailable"
_SKIP_REASONS = frozenset({SKIP_TIME_BUDGET, SKIP_SHM})


def _skip(into, name, reason=SKIP_TIME_BUDGET):
    """Record one skipped leg as ``{name}_skipped: reason`` (the key
    shape r4 pinned) and reject unknown reasons loudly."""
    if reason not in _SKIP_REASONS:
        raise ValueError(f"unknown skip reason: {reason!r}")
    into[f"{name}_skipped"] = reason
    return into


def _safe_ratio(num, den, nd=2):
    """Ratio of two measurements, or None when either side is missing,
    non-finite, or non-positive.  r5 shipped flash_vs_stock=Infinity
    because a sub-resolution denominator rounded to 0.0 — a ratio the
    artifact can't justify must be absent, not infinite."""
    try:
        num, den = float(num), float(den)
    except (TypeError, ValueError):
        return None
    if not (np.isfinite(num) and np.isfinite(den)) or num <= 0 or den <= 0:
        return None
    return round(num / den, nd)


def _roofline(bytes_ideal, bytes_moved, seconds=None):
    """Roofline-style HBM traffic row for one kernel leg.

    ``bytes_ideal`` is the compulsory traffic at this shape (inputs read
    once + outputs written once); ``bytes_moved`` what the measured
    implementation actually streams (analytic, from its blocking).
    ``traffic_ratio`` > 1 is the lowering's redundancy factor; with a
    measured ``seconds`` the achieved GB/s rides along.  Ratios go
    through ``_safe_ratio`` so a degenerate leg publishes an ABSENT
    number, never Infinity."""
    row = {"bytes_ideal": int(bytes_ideal),
           "bytes_moved": int(bytes_moved),
           "traffic_ratio": _safe_ratio(bytes_moved, bytes_ideal)}
    gbps = _safe_ratio(bytes_moved, (seconds or 0) * 1e9, nd=1)
    if gbps is not None:
        row["gbps_achieved"] = gbps
    return row


def _sanitize_json(obj):
    """Replace non-finite floats with None so the emitted report is
    strict JSON (json.dumps happily prints Infinity/NaN, which breaks
    every conforming parser downstream)."""
    if isinstance(obj, dict):
        return {k: _sanitize_json(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize_json(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def _error_keys(obj, prefix=""):
    """Every ``*error`` key in the report tree (sections record a raised
    exception as ``<name>_error``; child legs as ``child_error`` /
    ``error``)."""
    found = []
    if isinstance(obj, dict):
        for k, v in obj.items():
            path = f"{prefix}{k}"
            if str(k).endswith("error") and isinstance(v, str) and v:
                found.append(path)
            found += _error_keys(v, f"{path}.")
    return found


class _Watchdog:
    """Prints the (partially filled) report and exits NON-ZERO if the run
    outlives the budget by ``grace`` seconds — a wedged section cannot
    produce an empty artifact, and cannot pass for a finished run."""

    def __init__(self, report: dict, grace: float = 45.0):
        import threading

        self.report = report
        self._lock = threading.Lock()
        self._printed = False
        t = threading.Thread(target=self._arm, args=(grace,), daemon=True)
        t.start()

    def _arm(self, grace):
        delay = max(1.0, _BUDGET_S + grace - (time.time() - _T0))
        time.sleep(delay)
        if self.emit(tag="watchdog"):
            os._exit(1)

    def emit(self, tag: str = "") -> bool:
        with self._lock:
            if self._printed:
                return False
            self._printed = True
            if tag:
                self.report["extra"]["emitted_by"] = tag
            print(json.dumps(_sanitize_json(self.report)), flush=True)
            return True


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def build_step(model, tx, loss_fn, compute_dtype=None):
    import jax
    import jax.numpy as jnp
    import optax

    # the exact cast policy the framework ships (no drift between what is
    # measured and what Estimator runs)
    from analytics_zoo_tpu.train.estimator import _cast_floats, _cast_like

    def step(params, state, opt_state, xs, labels):
        def lossf(p):
            if compute_dtype is not None:
                p = _cast_floats(p, compute_dtype)
                xs_c = _cast_floats(xs, compute_dtype)
            else:
                xs_c = xs
            preds, ns = model.call(p, state, *xs_c, training=True)
            if compute_dtype is not None:
                preds = _cast_floats(preds, jnp.float32)
                ns = _cast_like(ns, state)
            return loss_fn(labels, preds), ns

        (loss, new_state), grads = jax.value_and_grad(
            lossf, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_state, new_opt,
                loss)

    return step


def _sync(x) -> None:
    """Device sync: ``block_until_ready`` (the barrier on the real
    device) plus a ONE-element host read of the result — never
    ``np.asarray(full_result)``, which would bill the whole transfer to
    the compute being measured.  In-order execution means syncing the
    last result drains the whole queue."""
    import jax
    import numpy as np_

    leaf = jax.tree_util.tree_leaves(x)[0]
    jax.block_until_ready(leaf)
    np_.asarray(leaf.ravel()[0] if getattr(leaf, "ndim", 0) else leaf)


def _time_steps(step, carry, args, warmup, iters):
    """Per-step device time via a two-point slope.

    Including the end-sync once in an N-step window inflates every step
    by sync/N.  Timing two windows (N and 2N) and taking the slope
    cancels the constant sync exactly while keeping the real pipelined
    per-dispatch cost in the number (steps serialize through the donated
    carry, so window time is genuinely N steps of device work)."""
    params, state, opt_state = carry
    for _ in range(warmup):
        params, state, opt_state, loss = step(params, state, opt_state,
                                              *args)
    _sync(loss)

    def window(n):
        nonlocal params, state, opt_state
        t0 = time.perf_counter()
        for _ in range(n):
            params, state, opt_state, loss = step(params, state, opt_state,
                                                  *args)
        _sync(loss)
        return time.perf_counter() - t0

    t1 = window(iters)
    t2 = window(2 * iters)
    if t2 > t1:
        return t2 - t1          # slope over `iters` steps
    return t1                   # noise guard: fall back to the window


# ---------------------------------------------------------------------------
# NCF throughput (the headline number)
# ---------------------------------------------------------------------------

def bench_ncf(device, batch=8192, warmup=1, iters=5, k_steps=64,
              compute_dtype=None):
    """Throughput of the framework's actual hot path: ``k_steps``
    optimizer steps fused into ONE dispatch via lax.scan over a stacked
    (K, B) superbatch — exactly what Estimator ships as
    ``steps_per_execution``.  Per-launch dispatch latency (the
    reference measured the same effect as >10%% Spark task-launch
    overhead, wp-bigdl.md:171) is amortized over the K steps, so the
    number reflects device compute, not launches."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.models import NeuralCF
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.nn.objectives import (
        sparse_categorical_crossentropy)
    from analytics_zoo_tpu.train.optimizers import Adam

    reset_name_scope()
    # MovieLens-1M shape, reference default hyper-params
    # (NeuralCF.scala:45: userEmbed/itemEmbed/mfEmbed=20, hidden 40/20/10)
    ncf = NeuralCF(user_count=6040, item_count=3706, class_num=5,
                   user_embed=20, item_embed=20, hidden_layers=(40, 20, 10),
                   mf_embed=20)
    model = ncf.model

    with jax.default_device(device):
        params, state = model.init(jax.random.PRNGKey(0))
        tx = Adam(lr=1e-3)
        opt_state = tx.init(params)
        step = build_step(model, tx, sparse_categorical_crossentropy,
                          compute_dtype=compute_dtype)

        def fused(params, state, opt_state, xs_stack, y_stack):
            def body(carry, bt):
                p, s, o = carry
                (bu, bi), by = bt
                p, s, o, loss = step(p, s, o, [bu, bi], by)
                return (p, s, o), loss

            (params, state, opt_state), losses = jax.lax.scan(
                body, (params, state, opt_state),
                ((xs_stack[0], xs_stack[1]), y_stack))
            return params, state, opt_state, losses[-1]

        fused = jax.jit(fused, donate_argnums=(0, 1, 2))
        # synthetic id stream generated ON DEVICE — a 100MB host
        # superbatch upload would tell us nothing about the training
        # engine being measured
        @jax.jit
        def gen(key):
            ku, ki, ky = jax.random.split(key, 3)
            return (jax.random.randint(ku, (k_steps, batch, 1), 1, 6041,
                                       jnp.int32),
                    jax.random.randint(ki, (k_steps, batch, 1), 1, 3707,
                                       jnp.int32),
                    jax.random.randint(ky, (k_steps, batch), 0, 5,
                                       jnp.int32))

        users, items, labels = gen(jax.random.PRNGKey(0))
        xs = [users, items]
        y = labels
        carry = (jax.device_put(params, device),
                 jax.device_put(state, device),
                 jax.device_put(opt_state, device))
        dt = _time_steps(fused, carry, (xs, y), warmup, iters)
    return batch * k_steps * iters / dt


# ---------------------------------------------------------------------------
# NCF convergence: negative-sampled MovieLens-1M-shaped run + HR@10
# ---------------------------------------------------------------------------

def _movielens_like(n_users=6040, n_items=3706, latent=8, pos_per_user=20,
                    seed=0):
    """MovieLens-1M-shaped implicit-feedback data with latent structure:
    each user's positives are drawn from their top-scoring items under a
    low-rank preference model, so a factorization model can actually
    learn it (and HR@10 separates trained from untrained)."""
    rs = np.random.RandomState(seed)
    zu = rs.randn(n_users + 1, latent).astype(np.float32)
    zi = rs.randn(n_items + 1, latent).astype(np.float32)
    scores = zu @ zi.T                                  # (U+1, I+1)
    scores[:, 0] = -np.inf                              # pad row
    # preference set = top ~8% of items (300 for the MovieLens-1M shape)
    top_k = min(300, max(pos_per_user + 1, n_items // 12))
    top = np.argpartition(-scores, top_k, axis=1)[:, :top_k]
    users, items, heldout = [], [], np.zeros(n_users + 1, np.int64)
    for u in range(1, n_users + 1):
        cand = top[u]
        cand = cand[cand > 0]
        picks = cand[rs.choice(len(cand), pos_per_user + 1, replace=False)]
        heldout[u] = picks[0]                           # test positive
        users.extend([u] * pos_per_user)
        items.extend(picks[1:].tolist())
    return (np.asarray(users, np.int64), np.asarray(items, np.int64),
            heldout, scores)


def bench_ncf_convergence(epochs=12, batch=2048, n_users=6040, n_items=3706,
                          n_eval=2000, embed=16, mf_embed=16,
                          hidden=(64, 32, 16), lr=2e-3, pos_per_user=50,
                          dropout=0.6, neg_per_pos=8, swa_from=3,
                          ensemble=1, seed=42, k_steps=128,
                          cpu_baseline_epochs=3):
    """The north star in ONE run: matched-accuracy convergence whose own
    sustained samples/sec is compared against a CPU run of the SAME code
    path (BASELINE.json: >=10x CPU at matched accuracy).

    The data path is fully device-resident: ALL epochs' negatives are
    sampled on-chip in one jitted program
    (``presample_implicit_epochs``), and ``Estimator.fit`` consumes
    epoch slices of the resident arrays directly — the epoch loop moves
    zero bytes host→device (r4's 120x gap between the fused microbench
    and the convergence run was host numpy sampling + per-epoch
    FeatureSet rebuild; both are gone).

    Recipe (r3 CPU sweep; r4 on-silicon): fresh negatives EVERY epoch, 8
    per positive; MODEST factors (embed 16 — embed 64 memorizes); MLP
    dropout 0.6; tail-averaged weights (SWA from ``swa_from``).
    Measured r4: single model 0.9255, 2-seed ensemble 0.929, against a
    practical bound of 0.9625 (MAP with true item factors; the 0.975
    "oracle" needs exact latent knowledge no training set conveys).
    Rejected knobs (measured no better): wd 1e-4/1e-5, cosine decay,
    wider GMF, longer training, late SWA, neg_per_pos 16.

    The CPU baseline runs ``cpu_baseline_epochs`` of the identical
    recipe on the host CPU backend (same Estimator, same presampler,
    same shapes — bit-identical programs, r4-proven) and reports its
    sustained post-compile throughput; set 0 to skip."""
    import jax as _jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.models import NeuralCF, presample_implicit_epochs
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.train.optimizers import Adam

    users, items, heldout, true_scores = _movielens_like(
        n_users, n_items, pos_per_user=pos_per_user)

    def train_member(member_seed, n_epochs, platform=None,
                     stream_frac=1.0):
        """One full convergence run; returns (model, history).

        ``stream_frac < 1`` trains on a leading slice of each epoch's
        stream — the per-chunk program (shapes, K, batch) is identical,
        only the chunk count drops, so per-sample throughput is the same
        measurement at a fraction of the wall cost (used to keep the CPU
        leg affordable: its dropout threefry makes CPU ~40k samples/s)."""
        init_zoo_context(steps_per_execution=k_steps, seed=member_seed,
                         platform=platform)
        reset_name_scope()
        dev = _jax.local_devices(backend=platform)[0] if platform else None
        ctxmgr = (_jax.default_device(dev) if dev is not None
                  else _contextlib.nullcontext())
        with ctxmgr:
            if stream_frac < 1.0:       # slice the positives up front so
                n_keep = max(batch, int(len(users) * stream_frac))
                use_u, use_i = users[:n_keep], items[:n_keep]
            else:                       # the presample cost shrinks too
                use_u, use_i = users, items
            tr_u, tr_i, tr_y = presample_implicit_epochs(
                use_u, use_i, n_items, epochs=n_epochs,
                neg_per_pos=neg_per_pos, seed=member_seed + 1,
                trim_multiple=batch, user_count=n_users)
            ncf = NeuralCF(user_count=n_users, item_count=n_items,
                           class_num=2, user_embed=embed, item_embed=embed,
                           hidden_layers=hidden, mf_embed=mf_embed,
                           dropout=dropout)
            ncf.compile(optimizer=Adam(lr=lr),
                        loss="sparse_categorical_crossentropy",
                        metrics=["accuracy"])
            avg, n_avg = None, 0
            for done in range(n_epochs):
                # epoch slices stay on device; the stream is pre-shuffled
                # per epoch by the presampler, so shuffle=False
                ncf.estimator.fit(
                    [tr_u[done][:, None], tr_i[done][:, None]], tr_y[done],
                    batch_size=batch, epochs=done + 1, shuffle=False,
                    verbose=False)
                if done + 1 >= swa_from:
                    cur = _jax.device_get(ncf.estimator.params)
                    if avg is None:
                        avg, n_avg = cur, 1
                    else:
                        n_avg += 1
                        avg = _jax.tree_util.tree_map(
                            lambda a, c: a + (c - a) / n_avg, avg, cur)
            # evaluate the tail-averaged weights (dropout is identity at
            # inference; no BN here, so no stat recompute)
            if avg is not None:
                ncf.estimator.set_initial_weights(
                    avg, _jax.device_get(ncf.estimator.state))
            return ncf, ncf.estimator.history

    t0 = time.perf_counter()
    # seed-ensemble: independently-trained members' softmax scores are
    # averaged at ranking time (each member's errors are partly
    # idiosyncratic; the mean sharpens the common latent signal)
    trained = [train_member(seed + 1000 * m, epochs)
               for m in range(max(1, ensemble))]
    train_s = time.perf_counter() - t0
    members = [t[0] for t in trained]
    # sustained = post-compile per-epoch throughput (epoch 1 carries the
    # XLA compiles); epochs 2+ are steady state
    epoch_tputs = [r["throughput"] for _, h in trained for r in h[1:]]
    sustained = float(np.median(epoch_tputs)) if epoch_tputs else 0.0
    samples_per_member = (len(users) * (1 + neg_per_pos) // batch) \
        * batch * epochs

    # HR@10, the NCF paper's protocol: held-out positive vs 99 negatives
    # the user has NOT interacted with (train positives + heldout are the
    # only exclusions — hard negatives from the latent preference set
    # remain eligible).  An oracle HR on the same candidate lists (ranking
    # by the true latent scores) calibrates the ceiling.
    rs = np.random.RandomState(2)
    n_eval = min(n_eval, n_users)       # subset of users for time-bound eval
    eval_users = rs.choice(np.arange(1, n_users + 1), n_eval, replace=False)
    seen = {int(u): {0} for u in eval_users}
    for u, i in zip(users, items):
        if int(u) in seen:
            seen[int(u)].add(int(i))
    all_u, all_i = [], []
    for u in eval_users:
        s = seen[int(u)]
        s.add(int(heldout[u]))
        negs = []
        while len(negs) < 99:
            j = int(rs.randint(1, n_items + 1))
            if j not in s:
                negs.append(j)
        all_u.extend([u] * 100)
        all_i.extend([int(heldout[u])] + negs)
    pu = np.asarray(all_u, np.int32)[:, None]
    pi = np.asarray(all_i, np.int32)[:, None]
    probs = np.mean([np.asarray(m.predict([pu, pi], batch_size=8192))
                     for m in members], axis=0)         # (N, 2) softmax
    pos_scores = probs[:, 1].reshape(n_eval, 100)
    ranks = (pos_scores[:, 1:] >= pos_scores[:, :1]).sum(axis=1)
    hr10 = float((ranks < 10).mean())
    oracle = true_scores[pu[:, 0], pi[:, 0]].reshape(n_eval, 100)
    oracle_hr10 = float(
        ((oracle[:, 1:] >= oracle[:, :1]).sum(axis=1) < 10).mean())
    samples = samples_per_member * len(members)
    out = {"hitrate_at_10": round(hr10, 4),
           "ensemble": len(members),
           "oracle_hitrate_at_10": round(oracle_hr10, 4),
           # r4 measured ceiling for ANY learner on this data: MAP user
           # estimation GIVEN the true item factors + generative link
           # reaches 0.9625 from 50 positives/user — the 0.975 oracle
           # needs exact latent knowledge no training set conveys
           # (docs/PERFORMANCE.md "the 0.975 oracle is not reachable").
           "practical_bound_hr10": 0.9625,
           "tpu_convergence_samples_per_sec": round(sustained, 1),
           "tpu_end_to_end_samples_per_sec": round(samples / train_s, 1),
           "train_samples": samples,
           "train_wall_s": round(train_s, 1)}
    if cpu_baseline_epochs > 0:
        try:
            t0 = time.perf_counter()
            # quarter-stream slice: identical per-chunk program, so the
            # per-sample rate is the same measurement at 1/4 the wall
            cpu_frac = 0.25
            _, cpu_hist = train_member(seed, cpu_baseline_epochs,
                                       platform="cpu",
                                       stream_frac=cpu_frac)
            cpu_wall = time.perf_counter() - t0
            cpu_tputs = [r["throughput"] for r in cpu_hist[1:]]
            # fallback (single-epoch history): wall-clock rate of the
            # quarter-stream run — scale the per-epoch sample count by
            # the SAME fraction the run actually trained on
            cpu_sustained = (float(np.median(cpu_tputs)) if cpu_tputs
                             else samples_per_member * cpu_frac
                             / epochs * cpu_baseline_epochs / cpu_wall)
            out["cpu_convergence_samples_per_sec"] = round(cpu_sustained, 1)
            out["cpu_baseline_epochs"] = cpu_baseline_epochs
            out["cpu_stream_frac"] = 0.25
            if cpu_sustained > 0:
                out["convergence_speedup_vs_cpu"] = round(
                    sustained / cpu_sustained, 2)
        except Exception as e:          # noqa: BLE001 — record, don't zero
            out["cpu_convergence_error"] = f"{type(e).__name__}: {e}"
    return out


# ---------------------------------------------------------------------------
# ResNet-50 (BASELINE config #2)
# ---------------------------------------------------------------------------

def bench_resnet50(device, batch=256, n1=4, rounds=2,
                   bn_stats_fraction=1.0):
    """ResNet-50 bf16 train step: ONE compiled program, launch-amortized.

    ONE uint8 batch (38.5MB, the serving wire format — normalize fuses
    into conv1) is uploaded — not a (K, B, 224, 224, 3) float32
    superbatch (2.47GB in one buffer); a fori_loop with RUNTIME trip
    count runs n and 2n optimizer steps through the same executable, and
    the slope cancels dispatch+sync exactly (per-step launch latency
    amortizes like steps_per_execution in production).  Parameter
    updates chain every iteration, so each step depends on the last."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.models.image.imageclassification import resnet50
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.nn.objectives import (
        sparse_categorical_crossentropy_with_logits)
    from analytics_zoo_tpu.train.optimizers import Adam

    reset_name_scope()
    model = resnet50(class_num=1000,   # logits head (fc, no softmax)
                     bn_stats_fraction=bn_stats_fraction)
    rs = np.random.RandomState(0)
    x_u8 = rs.randint(0, 256, (batch, 224, 224, 3)).astype(np.uint8)
    y = rs.randint(0, 1000, batch).astype(np.int32)

    with jax.default_device(device):
        params, state = model.init(jax.random.PRNGKey(0))
        tx = Adam(lr=1e-3)
        opt_state = tx.init(params)
        step = build_step(model, tx,
                          sparse_categorical_crossentropy_with_logits,
                          compute_dtype=jnp.bfloat16)

        @jax.jit
        def many(carry, xu8, yb, n):
            xb = [xu8.astype(jnp.float32) / 255.0]

            def body(_, c):
                p, s, o = c
                p, s, o, _loss = step(p, s, o, xb, yb)
                return (p, s, o)

            return jax.lax.fori_loop(0, n, body, carry)

        xd = jax.device_put(jnp.asarray(x_u8), device)
        yd = jax.device_put(jnp.asarray(y), device)
        carry = (jax.device_put(params, device),
                 jax.device_put(state, device),
                 jax.device_put(opt_state, device))
        _sync(many(carry, xd, yd, 1))          # compile + warm

        def t(n):
            t0 = time.perf_counter()
            _sync(many(carry, xd, yd, n))
            return time.perf_counter() - t0

        # distinct trip counts per dispatch + least-squares slope, as
        # in _measure_scan
        pts = [((r + 2) * n1, t((r + 2) * n1))
               for r in range(max(2, rounds))]
        ns = np.asarray([p[0] for p in pts], np.float64)
        ts = np.asarray([p[1] for p in pts], np.float64)
        denom = ((ns - ns.mean()) ** 2).sum()
        slope = ((ns - ns.mean()) * (ts - ts.mean())).sum() / denom
        per_step = max(slope, 1e-12)
    return batch / per_step


def bench_resnet_accuracy(device, n=4096, size=32, epochs=3, batch=256,
                          lr=3e-4):
    """Accuracy evidence for BASELINE config #2: a cold ResNet-50 trains
    to real VALIDATION accuracy through the full Estimator path on a
    dogs-vs-cats-shaped scene task (warm circles vs cool bars on noise —
    structured cues, fully separable, quoted ceiling 1.0).

    r5 post-mortem (the leg had never actually landed in any artifact):
    the original recipe paired resnet50's LOGITS head with the
    probability-space "sparse_categorical_crossentropy" — the net
    memorized the train set through the clipped loss and validated at
    CHANCE in every configuration until the with_logits loss was used
    (then 0.993 in 4 epochs).  bn_momentum=0.3 so the eval path's
    moving statistics converge within the leg's ~50 updates."""
    import jax

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.models.image.imageclassification import resnet50
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.train.optimizers import Adam

    import cv2

    def scene(kind, rs):
        img = (rs.rand(size, size, 3) * 60).astype(np.uint8)
        cx, cy = rs.randint(6, size - 6, 2)
        if kind:        # warm circle
            color = (int(rs.randint(0, 80)), int(rs.randint(60, 140)),
                     int(rs.randint(170, 255)))
            cv2.circle(img, (cx, cy), int(rs.randint(4, size // 4)),
                       color, -1)
        else:           # cool bar
            color = (int(rs.randint(170, 255)), int(rs.randint(60, 140)),
                     int(rs.randint(0, 80)))
            cv2.rectangle(img, (cx, cy),
                          (min(size - 1, cx + 12), min(size - 1, cy + 5)),
                          color, -1)
        return img.astype(np.float32) / 255.0

    init_zoo_context(compute_dtype="bfloat16", steps_per_execution=4)
    reset_name_scope()
    rs = np.random.RandomState(0)
    y = rs.randint(0, 2, n).astype(np.int32)
    x = np.stack([scene(int(t), rs) for t in y])
    split = int(0.9 * n)
    model = resnet50(class_num=2, input_shape=(size, size, 3),
                     bn_momentum=0.3)
    model.compile(optimizer=Adam(lr=lr),
                  loss="sparse_categorical_crossentropy_with_logits",
                  metrics=["accuracy"])
    t0 = time.perf_counter()
    model.fit(x[:split], y[:split], batch_size=batch, nb_epoch=epochs,
              verbose=False)
    dt = time.perf_counter() - t0
    res = model.evaluate(x[split:], y[split:], batch_size=512)
    return {"val_accuracy": round(float(res["accuracy"]), 4),
            "ceiling": 1.0, "epochs": epochs,
            "train_imgs_per_sec": round(split * epochs / dt, 1)}


# ---------------------------------------------------------------------------
# WideAndDeep (BASELINE config #4) + NNFrames pipeline (config #3)
# ---------------------------------------------------------------------------

def bench_wide_and_deep(device, batch=8192, k_steps=32, iters=3,
                        compute_dtype="bfloat16"):
    """WideAndDeep training throughput, census-shaped features
    (reference WideAndDeepExample.scala; BASELINE config #4): 2 wide
    cross columns, 2 embedding columns, 11 continuous — fused K-step
    dispatch like the NCF headline."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.models import WideAndDeep
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.nn.objectives import (
        sparse_categorical_crossentropy)
    from analytics_zoo_tpu.train.optimizers import Adam

    reset_name_scope()
    wnd = WideAndDeep(class_num=2, wide_base_dims=(1000, 1000),
                      embed_in_dims=(5000, 1000), embed_out_dims=(64, 64),
                      continuous_cols=11, hidden_layers=(100, 75, 50, 25))
    model = wnd.model
    rs = np.random.RandomState(0)
    wide = rs.randint(0, 1000, (k_steps, batch, 2)).astype(np.int32)
    wide[:, :, 1] += 1000
    emb = np.stack([rs.randint(0, 5000, (k_steps, batch)),
                    rs.randint(0, 1000, (k_steps, batch))],
                   axis=-1).astype(np.int32)
    cont = rs.randn(k_steps, batch, 11).astype(np.float32)
    yk = rs.randint(0, 2, (k_steps, batch)).astype(np.int32)

    with jax.default_device(device):
        params, state = model.init(jax.random.PRNGKey(0))
        tx = Adam(lr=1e-3)
        opt_state = tx.init(params)
        cd = jnp.bfloat16 if compute_dtype == "bfloat16" else None
        step = build_step(model, tx, sparse_categorical_crossentropy,
                          compute_dtype=cd)

        def fused(params, state, opt_state, xs_stack, y_stack):
            def body(carry, bt):
                p, s, o = carry
                (bw, be, bc), by = bt
                p, s, o, loss = step(p, s, o, [bw, be, bc], by)
                return (p, s, o), loss

            (params, state, opt_state), losses = jax.lax.scan(
                body, (params, state, opt_state),
                ((xs_stack[0], xs_stack[1], xs_stack[2]), y_stack))
            return params, state, opt_state, losses[-1]

        fused = jax.jit(fused, donate_argnums=(0, 1, 2))
        xs = [jax.device_put(jnp.asarray(a), device)
              for a in (wide, emb, cont)]
        yd = jax.device_put(jnp.asarray(yk), device)
        carry = (jax.device_put(params, device),
                 jax.device_put(state, device),
                 jax.device_put(opt_state, device))
        dt = _time_steps(fused, carry, (xs, yd), 1, iters)
    return batch * k_steps * iters / dt


def bench_data_paths(n_rows=1 << 20, batch=8192, epochs=3, k_steps=32):
    """Host-prefetch vs HBM-resident FeatureSet through the SAME
    ``Estimator.fit``: end-to-end samples/sec of both data paths on NCF-
    and WideAndDeep-shaped inputs, so the host-input gap closure (r5:
    NCF step compute 8.35M samples/s vs 891k end-to-end through the host
    path) is measured, not asserted.

    Per model two legs run: the default HOST path (background
    ``PrefetchIterator`` feeding the K-step fused program) and
    ``fs.cache("DEVICE")`` (one HBM materialization up front; per-epoch
    ``jax.random.permutation`` + gather inside ONE jitted fori_loop, so
    an epoch is one dispatch and zero host->device bytes).  Sustained =
    median post-compile epoch throughput (epoch 1 carries the XLA
    compile).  ``data_path`` records the route
    ``Estimator._resolve_data_path`` actually took, so a silently
    fallen-back device leg cannot masquerade as resident."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.data import FeatureSet
    from analytics_zoo_tpu.models import NeuralCF, WideAndDeep
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.train.optimizers import Adam

    rs = np.random.RandomState(0)
    n = max(batch, (n_rows // batch) * batch)

    def make_ncf():
        m = NeuralCF(user_count=6040, item_count=3706, class_num=2,
                     user_embed=16, item_embed=16, mf_embed=16,
                     hidden_layers=(64, 32, 16))
        xs = [rs.randint(1, 6041, (n, 1)).astype(np.int32),
              rs.randint(1, 3707, (n, 1)).astype(np.int32)]
        return m, xs

    def make_wnd():
        m = WideAndDeep(class_num=2, wide_base_dims=(1000, 1000),
                        embed_in_dims=(5000, 1000),
                        embed_out_dims=(64, 64), continuous_cols=11,
                        hidden_layers=(100, 75, 50, 25))
        wide = rs.randint(0, 1000, (n, 2)).astype(np.int32)
        wide[:, 1] += 1000                  # shared-table column offset
        emb = np.stack([rs.randint(0, 5000, n),
                        rs.randint(0, 1000, n)], axis=-1).astype(np.int32)
        cont = rs.randn(n, 11).astype(np.float32)
        return m, [wide, emb, cont]

    out = {}
    for name, make in (("ncf", make_ncf), ("wide_deep", make_wnd)):
        legs = {}
        for leg, level in (("host", None), ("device", "DEVICE")):
            init_zoo_context(steps_per_execution=k_steps, seed=0)
            reset_name_scope()
            model, xs = make()
            model.compile(optimizer=Adam(lr=1e-3),
                          loss="sparse_categorical_crossentropy")
            y = rs.randint(0, 2, n).astype(np.int32)
            fs = FeatureSet.from_ndarrays(xs, y, cache_level=level)
            est = model.estimator
            est.fit(fs, batch_size=batch, epochs=epochs, verbose=False)
            tputs = [r["throughput"] for r in est.history[1:]]
            legs[leg] = {
                "tpu_end_to_end_samples_per_sec": round(
                    float(np.median(tputs)) if tputs else 0.0, 1),
                "data_path": est.last_data_path,
            }
        host = legs["host"]["tpu_end_to_end_samples_per_sec"]
        dev = legs["device"]["tpu_end_to_end_samples_per_sec"]
        legs["device_vs_host"] = round(dev / host, 2) if host else None
        out[name] = legs
    return out


def bench_featureset_streaming(n_rows=1 << 15, batch=4096, epochs=3,
                               budget_frac=4):
    """STREAM tier vs whole-dataset residency through the SAME
    ``Estimator.fit`` (ISSUE 10): an NCF-shaped dataset sized
    ``budget_frac``× the device budget rotates budget-sized shards
    through HBM with the double-buffered uploader, against a resident
    leg whose budget fits the whole dataset.

    Reported per leg: sustained end-to-end samples/sec (median
    post-compile epoch) and the route the budget router actually took;
    plus ``stream_vs_resident`` (the acceptance floor is ≥0.5×) and the
    stream leg's ``data_stream_overlap_frac`` gauge — the counter-proof
    that uploads overlapped compute rather than serialising with it."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.data import FeatureSet
    from analytics_zoo_tpu.models import NeuralCF
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.observe import metrics as obs
    from analytics_zoo_tpu.train.optimizers import Adam

    rs = np.random.RandomState(0)
    n = max(batch, (n_rows // batch) * batch)
    xs_bytes = n * (4 + 4 + 4)          # user + item + label, int32

    def run(level, budget):
        init_zoo_context(steps_per_execution=1, seed=0)
        reset_name_scope()
        m = NeuralCF(user_count=6040, item_count=3706, class_num=2,
                     user_embed=16, item_embed=16, mf_embed=16,
                     hidden_layers=(64, 32, 16))
        m.compile(optimizer=Adam(lr=1e-3),
                  loss="sparse_categorical_crossentropy")
        xs = [rs.randint(1, 6041, (n, 1)).astype(np.int32),
              rs.randint(1, 3707, (n, 1)).astype(np.int32)]
        y = rs.randint(0, 2, n).astype(np.int32)
        fs = FeatureSet.from_ndarrays(xs, y, cache_level=level)
        est = m.estimator
        est.ctx.config.data_device_budget_bytes = budget
        est.fit(fs, batch_size=batch, epochs=epochs, verbose=False)
        tputs = [r["throughput"] for r in est.history[1:]]
        return est, {
            "tpu_end_to_end_samples_per_sec": round(
                float(np.median(tputs)) if tputs else 0.0, 1),
            "data_path": est.last_data_path,
        }

    out = {"dataset_bytes": xs_bytes,
           "device_budget_bytes": xs_bytes // budget_frac}
    _, resident = run("DEVICE", xs_bytes * 2)
    est_s, stream = run("STREAM", xs_bytes // budget_frac)
    snap = obs.METRICS.snapshot()
    stream["overlap_frac"] = round(float(
        snap.gauges.get(("data_stream_overlap_frac", ()), 0.0)), 3)
    if est_s._stream_plan is not None:
        stream["n_shards"] = est_s._stream_plan.n_shards
    out["resident"] = resident
    out["stream"] = stream
    res = resident["tpu_end_to_end_samples_per_sec"]
    out["stream_vs_resident"] = round(
        stream["tpu_end_to_end_samples_per_sec"] / res, 2) if res else None
    out["image"] = _bench_streaming_image_leg()
    return out


def _bench_streaming_image_leg(n=6144, batch=256, epochs=3,
                               budget_frac=4):
    """ResNet-shaped image leg of the streaming bench: float32
    32x32x3 rows trained through a small conv stem, with the device
    cache quantized to uint8 (``ZooConfig.data_cache_dtype``) so the
    rotation moves 4x fewer HBM bytes per shard than the host-side
    float payload.  Same contract as the NCF legs: STREAM at a
    ``budget_frac``x-over-budget dataset vs whole-dataset residency,
    both through the SAME ``Estimator.fit``."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.data import FeatureSet
    from analytics_zoo_tpu.nn import Sequential, reset_name_scope
    from analytics_zoo_tpu.nn.layers.convolutional import Convolution2D
    from analytics_zoo_tpu.nn.layers.core import Dense
    from analytics_zoo_tpu.nn.layers.pooling import GlobalAveragePooling2D
    from analytics_zoo_tpu.train.optimizers import Adam

    rs = np.random.RandomState(0)
    x = rs.randint(0, 256, (n, 32, 32, 3)).astype(np.float32)
    y = rs.randint(0, 10, n).astype(np.int32)
    # the budget is held against the CACHED (uint8) footprint — that is
    # what actually occupies HBM slots during the rotation
    cached_bytes = x.size * 1 + y.nbytes

    def run(level, budget):
        init_zoo_context(steps_per_execution=1, seed=0)
        reset_name_scope()
        m = Sequential()
        m.add(Convolution2D(16, 3, 3, subsample=2, activation="relu",
                            border_mode="same", input_shape=(32, 32, 3)))
        m.add(Convolution2D(32, 3, 3, subsample=2, activation="relu",
                            border_mode="same"))
        m.add(GlobalAveragePooling2D())
        m.add(Dense(10, activation="softmax"))
        m.compile(optimizer=Adam(lr=1e-3),
                  loss="sparse_categorical_crossentropy")
        est = m.estimator
        est.ctx.config.data_device_budget_bytes = budget
        est.ctx.config.data_cache_dtype = "uint8"
        fs = FeatureSet.from_ndarrays([x], y, cache_level=level)
        est.fit(fs, batch_size=batch, epochs=epochs, verbose=False)
        tputs = [r["throughput"] for r in est.history[1:]]
        return est, {
            "tpu_end_to_end_samples_per_sec": round(
                float(np.median(tputs)) if tputs else 0.0, 1),
            "data_path": est.last_data_path,
        }

    out = {"dataset_bytes": int(x.nbytes + y.nbytes),
           "cached_bytes": int(cached_bytes),
           "device_budget_bytes": int(cached_bytes // budget_frac)}
    # the router holds the budget against the HOST payload, so the
    # resident leg needs headroom over the float32 bytes
    _, resident = run("DEVICE", (x.nbytes + y.nbytes) * 2)
    est_s, stream = run("STREAM", cached_bytes // budget_frac)
    if est_s._stream_plan is not None:
        stream["n_shards"] = est_s._stream_plan.n_shards
    out["resident"] = resident
    out["stream"] = stream
    res = resident["tpu_end_to_end_samples_per_sec"]
    out["stream_vs_resident"] = round(
        stream["tpu_end_to_end_samples_per_sec"] / res, 2) if res else None
    return out


def bench_checkpoint_overhead(n=1 << 15, batch=4096, epochs=4,
                              k_steps=8):
    """Cost of the durability layer (docs/ROBUSTNESS.md): the SAME
    NCF-shaped ``Estimator.fit`` run three ways — no checkpointing,
    async per-epoch snapshots (the default: CRC32-manifested atomic
    writes land on a background thread), and fully synchronous saves —
    plus the raw latency of one verified save and one verified
    restore.  The async column is the claim under test: durability at
    per-epoch granularity should cost ~nothing on the step path."""
    import shutil
    import tempfile

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.models import NeuralCF
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.train import checkpoint as ckpt_lib
    from analytics_zoo_tpu.train.optimizers import Adam

    rs = np.random.RandomState(0)
    n = max(batch, (n // batch) * batch)
    out = {}
    est = None
    for leg, async_ckpt in (("no_ckpt", None), ("async", True),
                            ("sync", False)):
        init_zoo_context(steps_per_execution=k_steps, seed=0,
                         async_checkpoint=bool(async_ckpt))
        reset_name_scope()
        model = NeuralCF(user_count=6040, item_count=3706, class_num=2,
                         user_embed=16, item_embed=16, mf_embed=16,
                         hidden_layers=(64, 32, 16))
        xs = [rs.randint(1, 6041, (n, 1)).astype(np.int32),
              rs.randint(1, 3707, (n, 1)).astype(np.int32)]
        y = rs.randint(0, 2, n).astype(np.int32)
        model.compile(optimizer=Adam(lr=1e-3),
                      loss="sparse_categorical_crossentropy")
        est = model.estimator
        tmp = tempfile.mkdtemp(prefix="bench_ckpt_")
        try:
            if async_ckpt is not None:
                est.set_checkpoint(tmp)
            est.fit(xs, y, batch_size=batch, epochs=epochs,
                    verbose=False)
            tputs = [r["throughput"] for r in est.history[1:]]
            out[f"{leg}_samples_per_sec"] = round(
                float(np.median(tputs)) if tputs else 0.0, 1)
            if async_ckpt is not None and leg == "sync":
                # raw verified save/restore latency on the live snapshot
                mgr = ckpt_lib.CheckpointManager(tmp)
                t0 = time.perf_counter()
                mgr.save(est.global_step + 1, est._snapshot())
                out["save_verified_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 1)
                t0 = time.perf_counter()
                mgr.restore()
                out["restore_verified_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 1)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    base = out.get("no_ckpt_samples_per_sec") or 0
    for leg in ("async", "sync"):
        tput = out.get(f"{leg}_samples_per_sec")
        if base and tput:
            out[f"{leg}_overhead_pct"] = round(100 * (1 - tput / base), 1)
    return out


def bench_nnframes(n=120_000, epochs=2, batch=8192):
    """NNFrames end-to-end rows/sec (BASELINE config #3): DataFrame →
    NNEstimator.fit → NNModel.transform, including the pandas column
    extraction — the whole Spark-ML-shaped pipeline, not just the jitted
    step (reference NNEstimator.scala:414-491)."""
    import pandas as pd

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.nn import reset_name_scope
    from analytics_zoo_tpu.nn.layers.core import Dense
    from analytics_zoo_tpu.nn.topology import Sequential
    from analytics_zoo_tpu.nnframes import NNEstimator

    init_zoo_context(steps_per_execution=8)
    reset_name_scope()
    rs = np.random.RandomState(0)
    x = rs.randn(n, 16).astype(np.float32)
    yv = (x @ rs.randn(16)).astype(np.float32)
    df = pd.DataFrame({"features": list(x), "label": yv})

    m = Sequential()
    m.add(Dense(64, activation="relu", input_shape=(16,)))
    m.add(Dense(1))
    est = (NNEstimator(m, criterion="mse")
           .setBatchSize(batch).setMaxEpoch(epochs).setLearningRate(1e-3))
    t0 = time.perf_counter()
    nn_model = est.fit(df)
    fit_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = nn_model.transform(df)
    tr_s = time.perf_counter() - t0
    assert len(out) == n
    return {"fit_rows_per_sec": round(n * epochs / fit_s, 1),
            "transform_rows_per_sec": round(n / tr_s, 1)}


# ---------------------------------------------------------------------------
# Attention: Pallas flash kernel on silicon vs XLA blockwise fallback
# ---------------------------------------------------------------------------

def _scan_time_ms(fn, carry0, K=16, rounds=3, probe=True):
    """Per-call device time: K data-DEPENDENT applications fused in ONE
    dispatch via lax.scan, slope over (K, 2K) dispatches.

    Every iteration's input is derived from the previous output, the K
    iterations ride one dispatch (per-dispatch latency amortizes out),
    and the two-point slope cancels dispatch+sync exactly.
    ``fn(carry) -> array_like_carry``."""
    many = _make_scan_program(fn)
    _sync(many(carry0, K))              # compile + warm (one program)
    return _measure_scan(many, carry0, K, rounds, probe)


def _make_scan_program(fn):
    """ONE compile per case: the trip count is a RUNTIME argument
    (fori_loop lowers to while_loop), so the K and 2K windows share the
    same executable — compiling two scan programs per case blew a 536s
    attention section in the first r5 validation run."""
    import jax

    @jax.jit
    def many(c0, n):
        def body(_, c):
            out = fn(c)
            return 0.5 * c + 0.5 * out.astype(c.dtype)
        return jax.lax.fori_loop(0, n, body, c0)

    return many


def _measure_scan(many, carry0, K, rounds, probe=True):
    """Slope measurement of an already-warmed scan program.

    Every timed dispatch uses a distinct trip count (K, 2K, 3K, ...);
    the least-squares slope over the (n, t) points cancels the constant
    dispatch+sync cost exactly like the two-point version did.

    Returns the per-iteration time in ms, or None when the slope stays
    below timer resolution (< 0.5us/iter) after escalating the trip
    count — callers must treat None as "unresolved", never as 0.  r5
    published attention_l2048.flash_ms=0.0 / flash_vs_stock=Infinity
    from exactly this failure."""
    def t(n):
        t0 = time.perf_counter()
        _sync(many(carry0, n))
        return time.perf_counter() - t0

    # auto-scale K until the window dwarfs dispatch jitter.
    # The 64K probe ceiling matters for sub-microsecond iterations (the
    # attention_l2048 fwd legs): the old 4K cap left the whole window
    # inside timer resolution and the leg published null/unresolved
    while probe and K < 65536 and t(K + K // 4) < 0.08:
        K *= 4
    for attempt in range(5):
        pts = []
        for r in range(max(2, rounds + 1)):
            n = (r + 1) * K
            pts.append((n, t(n)))
        ns = np.asarray([p[0] for p in pts], np.float64)
        ts = np.asarray([p[1] for p in pts], np.float64)
        denom = ((ns - ns.mean()) ** 2).sum()
        slope_ms = float(((ns - ns.mean()) * (ts - ts.mean())).sum()
                         / denom) * 1e3
        if np.isfinite(slope_ms) and slope_ms >= 5e-4:
            return slope_ms
        # the whole window sat inside timer noise, so the fit
        # is garbage; grow the windows and retry while the budget holds
        if attempt == 4 or K >= (1 << 20) or _remaining() < 30.0:
            return None
        K *= 8
    return None


def _warm_parallel(cases, threads=6):
    """Compile+warm scan programs CONCURRENTLY (XLA compilation releases
    the GIL; measured r5: 3 flash-kernel programs compile in 33.6s
    threaded vs 82.0s serial).  ``cases``: iterable of (many, carry0).
    Errors are captured per-case and returned, not raised."""
    from concurrent.futures import ThreadPoolExecutor

    errs = {}

    def one(idx_case):
        idx, (many, carry0) = idx_case
        try:
            _sync(many(carry0, 1))
        except Exception as e:          # noqa: BLE001 — per-case report
            errs[idx] = e
    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(one, enumerate(cases)))
    return errs


def bench_attention(device, B=4, H=8, L=2048, D=64, K=None,
                    include_stock=True, include_bwd=True,
                    include_blockwise=True, blockwise_bwd=False,
                    rounds=3):
    """Hand-written Pallas flash kernel vs the XLA blockwise fallback vs
    the STOCK jax.experimental.pallas.ops.tpu flash kernel — the
    adopt-or-beat comparison, measured with the scan-fused timer (data
    dependence between iterations, one dispatch per window).
    ``include_bwd=False`` halves the compile bill for the secondary
    context lengths so all three lengths always fit the bench window."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.attention import blockwise_attention
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    if K is None:
        K = 4 if L >= 8192 else 16
    rs = np.random.RandomState(0)
    mk = lambda: jax.device_put(
        jnp.asarray(rs.randn(B, H, L, D).astype(np.float32)), device)
    q, k, v = mk(), mk(), mk()

    out = {}
    built = _build_attention_cases(out, q, k, v, D, K, rounds,
                                   include_stock, include_bwd,
                                   include_blockwise, blockwise_bwd)
    errs = _warm_parallel([(m, c) for _, m, c, _, _ in built])
    _finish_attention_cases(out, built, errs)
    _attention_roofline(out, B, H, L, D)
    return out


def _build_attention_cases(out, q, k, v, D, K, rounds, include_stock,
                           include_bwd, include_blockwise, blockwise_bwd):
    """Construct (key, many, carry, K, rounds) scan cases for one
    (q, k, v) shape — compilation deferred so a suite can warm every
    length's programs concurrently."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.attention import blockwise_attention
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    pairs = [("flash", lambda q, k, v: flash_attention(
                  q, k, v, causal=True))]
    if include_blockwise:
        pairs.append(("blockwise", lambda q, k, v: blockwise_attention(
            q, k, v, causal=True)))
    if include_stock:
        try:
            from jax.experimental.pallas.ops.tpu.flash_attention import (
                flash_attention as stock_flash)
            sm = 1.0 / float(np.sqrt(D))
            pairs.append(("stock_pallas",
                          lambda q, k, v: stock_flash(q, k, v, causal=True,
                                                      sm_scale=sm)))
        except Exception as e:
            out["stock_pallas_error"] = type(e).__name__
    built = []
    for name, fn in pairs:
        built.append((f"{name}_ms", _make_scan_program(
            lambda c, fn=fn: fn(c, k, v)), q, K, rounds))
        if include_bwd and (name != "blockwise" or blockwise_bwd):
            grad_q = jax.grad(lambda a, b, c, fn=fn: jnp.sum(fn(a, b, c)))
            built.append((f"{name}_fwdbwd_ms", _make_scan_program(
                lambda c, g=grad_q: g(c, k, v)), q, max(2, K // 2),
                rounds))
    return built


def _finish_attention_cases(out, built, errs):
    for idx, (key, many, carry, K, rounds) in enumerate(built):
        if idx in errs:                 # pallas unavailable / OOM etc.
            out[key.replace("_ms", "_error")] = type(errs[idx]).__name__
            continue
        try:
            ms = _measure_scan(many, carry, K, rounds)
        except Exception as e:          # noqa: BLE001
            out[key.replace("_ms", "_error")] = type(e).__name__
            continue
        if ms is None:
            out[key] = None
            out[key.replace("_ms", "_unresolved")] = \
                "slope below timer resolution after escalation"
        else:
            out[key] = round(ms, 3)
    for rkey, num, den in (
            ("flash_speedup", "blockwise_ms", "flash_ms"),
            ("flash_bwd_speedup", "blockwise_fwdbwd_ms", "flash_fwdbwd_ms"),
            ("flash_vs_stock", "stock_pallas_ms", "flash_ms")):
        if num in out and den in out:
            out[rkey] = _safe_ratio(out[num], out[den])


def _attention_roofline(out, B, H, L, D, bq=256):
    """Analytic HBM traffic for the causal flash fwd leg at this shape.

    Ideal = Q, K, V read once + O written once.  The kernel re-streams
    K/V tiles once per q block (causal: only tiles at or below the
    diagonal), so bytes-moved grows as L^2/bq — the pinned bytes row in
    docs/PERFORMANCE.md makes the blocking visible, not just the
    wall-clock."""
    f32 = 4
    ideal = f32 * B * H * D * 4 * L
    bq = min(bq, L)
    kv_rows = sum(min(L, (i + 1) * bq) for i in range(max(1, L // bq)))
    moved = f32 * B * H * D * (2 * L + 2 * kv_rows)
    ms = out.get("flash_ms")
    out["roofline_flash_fwd"] = _roofline(ideal, moved,
                                          ms * 1e-3 if ms else None)


def bench_attention_suite(device, specs, into=None):
    """All context lengths in one pass: BUILD every case, warm ALL
    programs concurrently (threaded XLA compile, ~2.4x wall), then
    measure serially on the quiet device.  ``specs``: [(L, kw), ...]."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    per_len = []
    all_cases = []
    for L, kw in specs:
        B, H, D = kw.pop("B", 4), kw.pop("H", 8), kw.pop("D", 64)
        K = kw.pop("K", 4 if L >= 8192 else 16)
        mk = lambda: jax.device_put(
            jnp.asarray(rs.randn(B, H, L, D).astype(np.float32)), device)
        q, k, v = mk(), mk(), mk()
        out = {}
        built = _build_attention_cases(
            out, q, k, v, D, K, kw.pop("rounds", 2),
            kw.pop("include_stock", True), kw.pop("include_bwd", True),
            kw.pop("include_blockwise", True),
            kw.pop("blockwise_bwd", False))
        per_len.append((L, (B, H, D), out, built, len(all_cases)))
        all_cases.extend((m, c) for _, m, c, _, _ in built)
    errs = _warm_parallel(all_cases)
    results = {}
    for L, (B, H, D), out, built, ofs in per_len:
        local_errs = {i - ofs: e for i, e in errs.items()
                      if ofs <= i < ofs + len(built)}
        # write INCREMENTALLY so a watchdog emit mid-suite still carries
        # every length measured so far
        if into is not None:
            into[f"attention_l{L}"] = out
        _finish_attention_cases(out, built, local_errs)
        _attention_roofline(out, B, H, L, D)
        results[f"attention_l{L}"] = out
    return results


# ---------------------------------------------------------------------------
# INT8 vs bf16/f32 matmul (the reference's int8-calibration ~2x claim,
# wp-bigdl.md:192, realised on the MXU's native int8 path)
# ---------------------------------------------------------------------------

def bench_int8(device, n=4096, K=128):
    """int8 MXU matmul vs bf16/f32 with the scan-fused timer (see
    _scan_time_ms) at n=4096 (a 64MB upload)."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.quantization import int8_dot, quantize_tensor

    rs = np.random.RandomState(0)
    x = jax.device_put(jnp.asarray(
        rs.randn(n, n).astype(np.float32)), device)
    w = rs.randn(n, n).astype(np.float32) * 0.1
    wq, wscale = quantize_tensor(w)
    wq = jax.device_put(wq, device)
    wscale = jax.device_put(jnp.asarray(wscale).reshape(-1), device)
    wd = jax.device_put(jnp.asarray(w), device)
    wbf = jax.device_put(jnp.asarray(w).astype(jnp.bfloat16), device)
    xscale = float(np.abs(rs.randn(10000)).max() / 127)

    out = {}
    # bf16 leg dropped from the artifact run: r5 measured bf16 within
    # 8% of f32 here (XLA computes f32 matmuls via bf16 passes on this
    # MXU), and each fori-program compile costs ~15s
    progs = {"f32_ms": _make_scan_program(lambda c: c @ wd),
             "int8_ms": _make_scan_program(
                 lambda c: int8_dot(c, wq, wscale, x_scale=xscale))}
    del wbf
    errs = _warm_parallel([(m, x) for m in progs.values()], threads=2)
    for idx, (key, many) in enumerate(progs.items()):
        if idx in errs:
            out[key.replace("_ms", "_error")] = type(errs[idx]).__name__
            continue
        ms = _measure_scan(many, x, K, rounds=2, probe=False)
        if ms is None:
            out[key] = None
            out[key.replace("_ms", "_unresolved")] = \
                "slope below timer resolution after escalation"
        else:
            out[key] = round(ms, 3)
    if "f32_ms" in out and "int8_ms" in out:
        out["int8_vs_f32_speedup"] = _safe_ratio(out["f32_ms"],
                                                 out["int8_ms"])
    if "bf16_ms" in out and "int8_ms" in out:
        out["int8_vs_bf16_speedup"] = _safe_ratio(out["bf16_ms"],
                                                  out["int8_ms"])
    return out


# ---------------------------------------------------------------------------
# ops/ fused kernels: embedding-bag gather-combine and dequantize-matmul
# vs their unfused XLA lowerings, with roofline bytes-moved rows alongside
# the wall-clock so the artifact records WHY the fusion wins, not just
# that it does
# ---------------------------------------------------------------------------


def _make_ids_scan(fn, vocab):
    """Scan program for an int32 ids carry: each iteration's bags derive
    from the previous output through a runtime-zero (but not provably
    zero) bump, so XLA cannot hoist the lookup out of the loop —
    _make_scan_program's data-dependence discipline, specialised to
    integer carries."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def many(c0, n):
        def body(_, ids):
            out = fn(ids)
            bump = (jnp.abs(out[0, 0]) * 1e-20).astype(jnp.int32)
            return (ids + bump + 1) % vocab
        return jax.lax.fori_loop(0, n, body, c0)

    return many


def _kernel_leg_recorder(leg: str, profile_ms: float = 50.0):
    """FlightRecorder armed over one kernel bench leg: a floor breach
    trigger()s a capture AND a short device profiler trace into
    BENCH_PROFILE_DIR/<leg> — the trace that explains a regression lands
    next to the artifact instead of needing a manual re-run under the
    profiler."""
    from analytics_zoo_tpu.observe.recorder import FlightRecorder

    root = os.environ.get("BENCH_PROFILE_DIR",
                          os.path.join(os.getcwd(), "bench_profile"))
    pdir = os.path.join(root, leg)
    return FlightRecorder(out_dir=pdir, profile_dir=pdir,
                          profile_ms=profile_ms)


def _breach_check(out, leg, ratio_key, floor):
    """Capture a flight record + device profile when a speedup floor is
    breached; an unresolved ratio is NOT a breach (absent, not zero)."""
    spd = out.get(ratio_key)
    if spd is None or spd >= floor:
        return
    try:
        out["breach_flight_record"] = _kernel_leg_recorder(leg).trigger(
            f"{leg}_speedup_breach", {ratio_key: spd, "floor": floor})
    except Exception as e:      # noqa: BLE001 — never fail the leg
        out["breach_recorder_error"] = f"{type(e).__name__}: {e}"


def bench_embedding_bag(device, V=1 << 20, D=64, B=4096, N=32, K=16,
                        rounds=2):
    """Fused Pallas embedding-bag vs the unfused XLA gather+segment-sum
    at a DLRM-ish shape (1M-row table, 32-hot bags), scan-fused timing
    over an ids carry.  The roofline rows expose the mechanism: the
    unfused lowering writes the (B, N, D) gathered rows to HBM and
    reads them back for the reduce — ~3x the compulsory traffic the
    fused kernel moves."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.embedding_bag import (
        embedding_bag, embedding_bag_reference)

    rs = np.random.RandomState(0)
    table = jax.device_put(jnp.asarray(
        rs.randn(V, D).astype(np.float32) * 0.05), device)
    ids = jax.device_put(jnp.asarray(
        rs.randint(0, V, size=(B, N)).astype(np.int32)), device)

    out = {"shape": {"vocab": V, "dim": D, "bags": B, "multi_hot": N}}
    progs = {
        "fused_ms": _make_ids_scan(
            lambda c: embedding_bag(table, c, "sum", None), V),
        "unfused_ms": _make_ids_scan(
            lambda c: embedding_bag_reference(table, c, "sum", None), V),
    }
    errs = _warm_parallel([(m, ids) for m in progs.values()], threads=2)
    for idx, (key, many) in enumerate(progs.items()):
        if idx in errs:
            out[key.replace("_ms", "_error")] = type(errs[idx]).__name__
            continue
        ms = _measure_scan(many, ids, K, rounds=rounds)
        if ms is None:
            out[key] = None
            out[key.replace("_ms", "_unresolved")] = \
                "slope below timer resolution after escalation"
        else:
            out[key] = round(ms, 3)
    out["fused_vs_unfused_speedup"] = _safe_ratio(
        out.get("unfused_ms"), out.get("fused_ms"))
    ideal = 4 * (B * N * D + B * D)     # rows read once + bags written
    fsec = out.get("fused_ms")
    usec = out.get("unfused_ms")
    out["roofline_fused"] = _roofline(
        ideal, ideal, fsec * 1e-3 if fsec else None)
    out["roofline_unfused"] = _roofline(
        ideal, 4 * (3 * B * N * D + B * D),
        usec * 1e-3 if usec else None)
    if jax.default_backend() == "tpu":
        # the acceptance floor only binds where the Pallas path runs
        _breach_check(out, "embedding_bag", "fused_vs_unfused_speedup",
                      1.3)
    return out


def bench_dlrm_sharded_child(giant=True, v_train=1 << 20, d_train=16,
                             b=4096, n=8, k_steps=8, rounds=3,
                             v_giant=100_000_000, d_giant=2,
                             b_giant=8192):
    """Measured legs of the DLRM sharded-embedding bench; runs in the
    subprocess ``bench_dlrm_sharded`` launches (dp×tp mesh over however
    many devices the child sees).  Three legs:

    - parity: sharded lookup vs the dense ``embedding_bag`` at rtol
      1e-6 on a small table (the correctness gate on everything below);
    - train: a table the bench budget cannot hold replicated (router
      must pick ``sharded``) trained for real steps — samples/sec, the
      per-chip table HBM actually resident, the Adam moments' placement,
      and the replicated twin's throughput for the speedup row;
    - giant (optional): a 10⁸-row table initialized shard-by-shard
      straight from the lazy ``SyntheticGiantTable`` generator — never
      materialized on the host — then timed on sharded lookups.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from analytics_zoo_tpu.core.context import init_zoo_context
    from analytics_zoo_tpu.data.giant_table import SyntheticGiantTable
    from analytics_zoo_tpu.ops.embedding_bag import embedding_bag
    from analytics_zoo_tpu.parallel.table_sharding import (
        choose_table_placement, init_table_sharded, sharded_bag,
        sharded_gather)

    ndev = len(jax.devices())
    ways = 4 if ndev % 4 == 0 and ndev >= 8 else \
        (2 if ndev % 2 == 0 else 1)
    ctx = init_zoo_context(mesh_shape=(ndev // ways, ways),
                           axis_names=("data", "model"))
    mesh = ctx.mesh
    out = {"mesh": {"data": ndev // ways, "model": ways},
           "platform": jax.devices()[0].platform}
    rs = np.random.RandomState(0)

    # --- parity gate: sharded vs dense bag on a small table ----------
    tb = jnp.asarray(rs.randn(256, 16).astype(np.float32) * 0.05)
    pid = jnp.asarray(rs.randint(0, 256, (64, 8)).astype(np.int32))
    ref = np.asarray(embedding_bag(tb, pid, "sum", None))
    got = np.asarray(sharded_bag(tb, pid, "sum", None, mesh=mesh,
                                 axis="model"))
    out["parity_max_abs_err"] = float(np.max(np.abs(ref - got)))
    out["parity_ok"] = bool(np.allclose(ref, got, rtol=1e-6, atol=1e-7))

    def timed(fn, *args):
        """min seconds per call over ``rounds`` of ``k_steps`` calls."""
        best = None
        res = fn(*args)                          # warm/compile
        jax.block_until_ready(res)
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(k_steps):
                res = fn(*args) if not isinstance(res, tuple) else \
                    fn(*res)
            jax.block_until_ready(res)
            dt = (time.perf_counter() - t0) / k_steps
            best = dt if best is None else min(best, dt)
        return best, res

    # --- train leg: a table that does NOT fit replicated -------------
    nbytes = v_train * d_train * 4
    budget = nbytes // 2                 # replicated over, /ways under
    dec = choose_table_placement(nbytes=nbytes, rows=v_train,
                                 requested="auto", mesh=mesh,
                                 axis="model", budget_bytes=budget)
    train = {"rows": v_train, "dim": d_train, "nbytes": nbytes,
             "budget_bytes": budget, "router_placement": dec.placement,
             "router_reason": dec.reason_code}
    out["train"] = train
    host_table = rs.randn(v_train, d_train).astype(np.float32) * 0.05
    ids_h = rs.randint(0, v_train, (b, n)).astype(np.int32)
    y_h = rs.randn(b, d_train).astype(np.float32)
    tx = optax.adam(1e-3)
    d_sh = NamedSharding(mesh, P("data", None))
    ids = jax.device_put(jnp.asarray(ids_h), d_sh)
    y = jax.device_put(jnp.asarray(y_h), d_sh)

    def make_step(lookup):
        def loss_fn(tab):
            return jnp.mean((lookup(tab) - y) ** 2)

        @jax.jit
        def step(tab, opt):
            g = jax.grad(loss_fn)(tab)
            upd, opt = tx.update(g, opt, tab)
            return optax.apply_updates(tab, upd), opt
        return step

    table = jax.device_put(jnp.asarray(host_table),
                           NamedSharding(mesh, P("model", None)))
    opt0 = jax.jit(tx.init)(table)
    sec, (table_out, opt_out) = timed(
        make_step(lambda t: sharded_bag(t, ids, "sum", None, mesh=mesh,
                                        axis="model")), table, opt0)
    train["sharded_samples_per_sec"] = round(b / sec, 1) if sec else None
    train["hbm_table_bytes_per_chip"] = int(
        table_out.addressable_shards[0].data.nbytes)
    mu = jax.tree_util.tree_leaves(opt_out)
    moment = next((x for x in mu if getattr(x, "shape", ()) ==
                   table.shape), None)
    train["adam_moments_sharded"] = bool(
        moment is not None and
        moment.addressable_shards[0].data.shape[0] < table.shape[0])
    # replicated twin (same steps, dense bag) for the speedup row
    rep = jax.device_put(jnp.asarray(host_table),
                         NamedSharding(mesh, P()))
    sec_r, _ = timed(make_step(
        lambda t: embedding_bag(t, ids, "sum", None)), rep,
        jax.jit(tx.init)(rep))
    train["replicated_samples_per_sec"] = \
        round(b / sec_r, 1) if sec_r else None
    train["sharded_vs_replicated_speedup"] = _safe_ratio(
        train["sharded_samples_per_sec"],
        train["replicated_samples_per_sec"])

    # --- giant leg: 10⁸ rows, lazily generated, shard-resident -------
    if giant:
        src = SyntheticGiantTable(v_giant, d_giant, seed=11)
        t0 = time.time()
        gt = init_table_sharded(mesh, v_giant, d_giant, src,
                                axis="model")
        jax.block_until_ready(gt)
        g = {"rows": v_giant, "dim": d_giant, "nbytes": src.nbytes,
             "init_seconds": round(time.time() - t0, 1),
             "hbm_bytes_per_chip": int(
                 gt.addressable_shards[0].data.nbytes)}
        out["giant"] = g
        gids_h = rs.randint(0, v_giant, (b_giant,)).astype(np.int32)
        gids = jax.device_put(jnp.asarray(gids_h),
                              NamedSharding(mesh, P("data")))
        lookup = jax.jit(lambda t, i: sharded_gather(t, i, mesh=mesh,
                                                     axis="model"))
        sec_g, _ = timed(lookup, gt, gids)
        g["lookup_samples_per_sec"] = \
            round(b_giant / sec_g, 1) if sec_g else None
        # compulsory = touched rows read once + output written once;
        # the replicated lowering's moved bytes at this shape (every
        # lookup reads its row, no dedup) quantify what dedup could buy
        uniq = int(np.unique(gids_h).size)
        ideal = (uniq + b_giant) * d_giant * 4
        moved = 2 * b_giant * d_giant * 4
        g["roofline_replicated_lookup"] = _roofline(ideal, moved, sec_g)
    return out


def bench_dlrm_sharded(giant=True):
    """DLRM-scale sharded-embedding evidence (ISSUE 14).

    The ``geometry`` rows are pure arithmetic — per-chip table HBM under
    ``model``-axis sharding vs replicated, and the per-step exchange
    payload (the combined (B, D) psum) vs the (B, N, D) allgather a
    replicated-output lowering would move — deterministic, so the doc of
    record pins them.  The measured legs (parity, sharded-vs-replicated
    training, the 10⁸-row lazily-initialized lookup) run in a subprocess
    with a forced 8-device CPU dryrun mesh (its rows carry
    ``device.platform == "cpu"``): the geometry is identical on real
    silicon; the timings are not device numbers.
    """
    import subprocess
    import sys

    B, N, D_TRAIN = 4096, 8, 16
    WAYS = 4
    V_GIANT, D_GIANT = 100_000_000, 2
    giant_nbytes = V_GIANT * D_GIANT * 4
    out = {"geometry": {
        "giant_rows": V_GIANT,
        "giant_dim": D_GIANT,
        "giant_table_nbytes": giant_nbytes,
        "model_axis_ways": WAYS,
        "hbm_table_bytes_per_chip_sharded": giant_nbytes // WAYS,
        "hbm_table_bytes_per_chip_replicated": giant_nbytes,
        "hbm_chip_ratio": _safe_ratio(giant_nbytes,
                                      giant_nbytes // WAYS),
        "exchange_payload_bytes_per_step": B * D_TRAIN * 4,
        "allgather_bytes_per_step": B * N * D_TRAIN * 4,
        "exchange_vs_allgather_ratio": _safe_ratio(
            B * N * D_TRAIN * 4, B * D_TRAIN * 4),
    }}
    code = (
        "import os;"
        "os.environ['JAX_PLATFORMS']='cpu';"
        "os.environ['XLA_FLAGS']=os.environ.get('XLA_FLAGS','')"
        "+' --xla_force_host_platform_device_count=8';"
        "import sys, json; sys.path.insert(0, os.getcwd());"
        "from bench import bench_dlrm_sharded_child;"
        "from analytics_zoo_tpu.core.context import describe_devices;"
        f"print('DLRMJSON', json.dumps(dict(bench_dlrm_sharded_child("
        f"giant={bool(giant)}), device=describe_devices())))")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=max(60, min(420, _remaining() - 20)),
            cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in proc.stdout.splitlines():
            if line.startswith("DLRMJSON "):
                out.update(json.loads(line[len("DLRMJSON "):]))
                break
        else:
            out["child_error"] = (f"child rc={proc.returncode}: "
                                  f"{(proc.stderr or '')[-400:]}")
    except Exception as e:
        out["child_error"] = f"{type(e).__name__}: {e}"
    return out


def bench_table_hot_cache_child(tiny=False):
    """Measured + deterministic legs of the zipfian hot-cache/dedup
    bench (ISSUE 19); runs in the subprocess ``bench_table_hot_cache``
    launches (dp×tp mesh over the devices the child sees), or directly
    in the CI smoke with ``tiny=True``.

    - ``geometry``: pure arithmetic on the SHARED seeded zipf draw
      (``data.zipf.zipfian_ids`` — byte-identical to the loadgen
      payload class): steady-state hit rate, cold-unique counts, and
      the exchange/HBM bytes-moved reductions vs the uncached lookup —
      deterministic, so the doc of record pins them, and the ≥5×
      reduction gate at s=1.0 is asserted right here;
    - ``parity``: cached-vs-uncached gather AND bag on a real sharded
      mesh table at rtol 1e-6 (the correctness gate on the savings);
    - ``dedup``: dedup-vs-naive sharded lookup, forward and gradient;
    - ``timing_ms``: honest wall-clock of both paths (not gated — on a
      CPU dryrun mesh the host-routed cache mostly proves overheads).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from analytics_zoo_tpu.core.context import init_zoo_context
    from analytics_zoo_tpu.data.zipf import zipfian_ids
    from analytics_zoo_tpu.parallel.hot_cache import (
        HotRowCache, cached_sharded_bag, cached_sharded_gather,
        cold_bucket, table_row_reader)
    from analytics_zoo_tpu.parallel.table_sharding import (sharded_bag,
                                                           sharded_gather)

    if tiny:
        V, D, K, B, NBAG, S = 256, 8, 64, 1024, 4, 1.0
    else:
        V, D, K, B, NBAG, S = 4096, 64, 1024, 16384, 8, 1.0

    ndev = len(jax.devices())
    ways = 4 if ndev % 4 == 0 and ndev >= 8 else \
        (2 if ndev % 2 == 0 else 1)
    ctx = init_zoo_context(mesh_shape=(ndev // ways, ways),
                           axis_names=("data", "model"))
    mesh = ctx.mesh
    out = {"mesh": {"data": ndev // ways, "model": ways},
           "platform": jax.devices()[0].platform, "tiny": bool(tiny)}

    # --- geometry: deterministic, from the shared seeded draw --------
    warm = zipfian_ids(V, 4 * B, S, seed=0)   # the batcher's stream
    meas = zipfian_ids(V, B, S, seed=1)       # the measured batch
    counts = np.bincount(warm, minlength=V)
    order = np.lexsort((np.arange(V), -counts))   # count desc, id asc
    hot_ids = np.sort(order[:K])
    hot = np.isin(meas, hot_ids)
    cold_unique = int(np.unique(meas[~hot]).size)
    bucket = cold_bucket(cold_unique) if cold_unique else 0
    geometry = {
        "vocab": V, "dim": D, "capacity": K, "ids_per_batch": B,
        "skew_s": S,
        "hit_rate": round(float(hot.mean()), 4),
        "unique_ids_per_batch": int(np.unique(meas).size),
        "cold_unique_ids": cold_unique,
        "cold_bucket": bucket,
        # exchange: every uncached id rides the (B, D) psum; cached,
        # only the deduped cold bucket does (none at all when fully hot)
        "exchange_bytes_uncached": B * D * 4,
        "exchange_bytes_cached_ideal": cold_unique * D * 4,
        "exchange_bytes_cached_bucketed": bucket * D * 4,
        "exchange_reduction_ideal": _safe_ratio(B * D * 4,
                                                cold_unique * D * 4),
        "exchange_reduction_bucketed": _safe_ratio(B * D * 4,
                                                   bucket * D * 4),
        # HBM: naive reads one big-table row per slot; dedup+cache
        # reads each distinct cold row once (hot rows serve from the
        # K-row host-side replica, touching no HBM at all)
        "hbm_rows_touched_naive": B,
        "hbm_rows_touched_dedup_cached": cold_unique,
        "hbm_reduction": _safe_ratio(B, cold_unique),
        # the contrast row: the same cache under UNIFORM traffic —
        # skew is what pays for the replica, not the mechanism
        "uniform_hit_rate": round(float(np.isin(
            zipfian_ids(V, B, 0.0, seed=2), hot_ids).mean()), 4),
    }
    red = geometry["exchange_reduction_ideal"]
    geometry["reduction_gate_ok"] = bool(red is not None and red >= 5.0)
    out["geometry"] = geometry
    if not tiny and not geometry["reduction_gate_ok"]:
        raise AssertionError(
            f"exchange reduction {red} < 5x at s={S} "
            f"(V={V} K={K} B={B}) — the ISSUE 19 acceptance floor")

    # --- measured parity on a real sharded mesh table ----------------
    rs = np.random.RandomState(0)
    table = jax.device_put(
        jnp.asarray(rs.randn(V, D).astype(np.float32) * 0.05),
        NamedSharding(mesh, P("model", None)))
    cache = HotRowCache("bench/table", capacity=K, dim=D, mesh=mesh)
    cache.record(warm)
    cache.refresh(table_row_reader(table))
    with jax.transfer_guard("allow"):
        want = np.asarray(jax.device_get(sharded_gather(
            table, jnp.asarray(meas.astype(np.int32)), mesh=mesh,
            axis="model")))
    got = cached_sharded_gather(cache, table, meas, mesh=mesh,
                                axis="model", record=False)
    bag_ids = meas[:(B // NBAG) * NBAG].reshape(-1, NBAG)
    with jax.transfer_guard("allow"):
        want_bag = np.asarray(jax.device_get(sharded_bag(
            table, jnp.asarray(bag_ids.astype(np.int32)), "mean",
            pad_id=None, mesh=mesh, axis="model")))
    got_bag = cached_sharded_bag(cache, table, bag_ids, "mean",
                                 pad_id=None, mesh=mesh, axis="model",
                                 record=False)
    out["parity"] = {
        "gather_max_abs_err": float(np.max(np.abs(want - got))),
        "gather_ok": bool(np.allclose(want, got, rtol=1e-6, atol=1e-7)),
        "bag_max_abs_err": float(np.max(np.abs(want_bag - got_bag))),
        "bag_ok": bool(np.allclose(want_bag, got_bag, rtol=1e-6,
                                   atol=1e-7)),
        "measured_hit_rate": round(cache.stats()["hit_rate"], 4),
    }
    if not (out["parity"]["gather_ok"] and out["parity"]["bag_ok"]):
        raise AssertionError(f"cache parity breach: {out['parity']}")

    # --- dedup-vs-naive sharded lookup, forward and gradient ---------
    ids_j = jnp.asarray(bag_ids.astype(np.int32))

    def loss(tab, dedup):
        return jnp.sum(sharded_bag(tab, ids_j, "sum", pad_id=None,
                                   mesh=mesh, axis="model",
                                   dedup=dedup) ** 2)

    f_d = np.asarray(sharded_bag(table, ids_j, "sum", pad_id=None,
                                 mesh=mesh, axis="model", dedup=True))
    f_n = np.asarray(sharded_bag(table, ids_j, "sum", pad_id=None,
                                 mesh=mesh, axis="model", dedup=False))
    g_d = np.asarray(jax.grad(lambda t: loss(t, True))(table))
    g_n = np.asarray(jax.grad(lambda t: loss(t, False))(table))
    out["dedup"] = {
        "fwd_max_abs_err": float(np.max(np.abs(f_d - f_n))),
        "fwd_ok": bool(np.allclose(f_d, f_n, rtol=1e-6, atol=1e-7)),
        "grad_max_abs_err": float(np.max(np.abs(g_d - g_n))),
        "grad_ok": bool(np.allclose(g_d, g_n, rtol=1e-6, atol=1e-6)),
    }
    if not (out["dedup"]["fwd_ok"] and out["dedup"]["grad_ok"]):
        raise AssertionError(f"dedup parity breach: {out['dedup']}")

    # --- honest wall-clock of both lookup paths ----------------------
    def wall(fn, reps=3):
        fn()                                     # warm/compile
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return round(best * 1e3, 3)

    ids_dev = jnp.asarray(meas.astype(np.int32))
    uncached = jax.jit(lambda t, i: sharded_gather(t, i, mesh=mesh,
                                                   axis="model"))
    out["timing_ms"] = {
        "uncached_gather": wall(lambda: jax.block_until_ready(
            uncached(table, ids_dev))),
        "cached_gather": wall(lambda: cached_sharded_gather(
            cache, table, meas, mesh=mesh, axis="model", record=False)),
    }
    return out


def bench_table_hot_cache():
    """Zipfian hot-row cache + dedup evidence (ISSUE 19) — geometry,
    parity, and timing from :func:`bench_table_hot_cache_child` in a
    subprocess with a forced 8-device CPU dryrun mesh (its rows carry
    ``device.platform == "cpu"``; the geometry rows are identical on real
    silicon, the timings are not device numbers)."""
    import subprocess
    import sys

    out = {}
    code = (
        "import os;"
        "os.environ['JAX_PLATFORMS']='cpu';"
        "os.environ['XLA_FLAGS']=os.environ.get('XLA_FLAGS','')"
        "+' --xla_force_host_platform_device_count=8';"
        "import sys, json; sys.path.insert(0, os.getcwd());"
        "from bench import bench_table_hot_cache_child;"
        "from analytics_zoo_tpu.core.context import describe_devices;"
        "print('HOTCACHEJSON', json.dumps(dict("
        "bench_table_hot_cache_child(), device=describe_devices())))")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=max(60, min(300, _remaining() - 20)),
            cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in proc.stdout.splitlines():
            if line.startswith("HOTCACHEJSON "):
                out.update(json.loads(line[len("HOTCACHEJSON "):]))
                break
        else:
            out["child_error"] = (f"child rc={proc.returncode}: "
                                  f"{(proc.stderr or '')[-400:]}")
    except Exception as e:
        out["child_error"] = f"{type(e).__name__}: {e}"
    return out


def ring_attention_geometry(L, ways, B=1, H=8, D=64, dtype_bytes=4):
    """Pure-arithmetic ICI-traffic and residency rows for one ring
    configuration (ISSUE 17) — deterministic, so docs/PERFORMANCE.md
    pins them and ``tests/test_ring_attention.py`` machine-checks the
    pinned table against this function.

    Per hop every chip forwards its resident K AND V chunk one
    neighbour over: ``2·(L/ways)·D·dtype`` bytes per link per step,
    ``ways-1`` steps, each overlapped with that hop's attention compute
    (double-buffered ppermute).  An allgather lowering moves the same
    total ``(ways-1)·2·(L/ways)·D·dtype`` but as one up-front burst
    with nothing to overlap — and then holds the FULL gathered K/V per
    chip, which is exactly the O(L) residency the ring avoids: the ring
    keeps resident + in-flight chunk pairs only, O(L/ways) per chip.
    """
    per_chip = L // ways
    kv_chunk = B * H * per_chip * D * dtype_bytes    # one of K or V
    inbound = (ways - 1) * 2 * kv_chunk   # compulsory remote K/V bytes
    return {
        "l": L, "ways": ways, "tokens_per_chip": per_chip,
        "ring_bytes_per_step_per_link": 2 * kv_chunk,
        "ring_total_ici_bytes_per_chip": inbound,
        "allgather_burst_bytes_per_chip": inbound,
        "peak_kv_bytes_per_chip_ring": 4 * kv_chunk,
        "peak_kv_bytes_per_chip_gathered": 2 * ways * per_chip * B * H
        * D * dtype_bytes,
        "peak_kv_ratio": _safe_ratio(2 * ways * kv_chunk, 4 * kv_chunk),
        # traffic_ratio 1.0: the ring moves exactly the compulsory
        # remote-K/V bytes — no lowering can move less and still attend
        "roofline_ring_ici": _roofline(inbound, inbound),
    }


def bench_ring_attention_child(L=4096, ways=4, B=1, H=4, D=64,
                               k_steps=4, rounds=2):
    """Measured legs of the ring-attention bench (runs in the forced
    8-device subprocess ``bench_ring_attention`` launches): samples/sec
    of the sequence-sharded ring vs single-chip blockwise flash at the
    same shape, plus fwd parity."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.attention import blockwise_attention
    from analytics_zoo_tpu.ops.ring_attention import ring_attention
    from analytics_zoo_tpu.parallel.sharding import seq_mesh

    rs = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rs.randn(B, H, L, D).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    mesh = seq_mesh(ways)
    out = {"l": L, "ways": ways, "batch": B, "heads": H, "head_dim": D}

    ring = jax.jit(lambda a, b_, c: ring_attention(
        a, b_, c, mesh=mesh, causal=True, knob="on"))
    single = jax.jit(lambda a, b_, c: blockwise_attention(
        a, b_, c, causal=True))
    o_r = jax.block_until_ready(ring(q, k, v))
    o_s = jax.block_until_ready(single(q, k, v))
    out["parity_max_err"] = float(jnp.abs(o_r - o_s).max())

    def timed(fn):
        best = None
        for _ in range(rounds):
            t0 = time.perf_counter()
            r = None
            for _ in range(k_steps):
                r = fn(q, k, v)
            jax.block_until_ready(r)
            dt = (time.perf_counter() - t0) / k_steps
            best = dt if best is None else min(best, dt)
        return best

    sec_r, sec_s = timed(ring), timed(single)
    out["ring_samples_per_sec"] = round(B / sec_r, 2) if sec_r else None
    out["single_chip_samples_per_sec"] = \
        round(B / sec_s, 2) if sec_s else None
    out["ring_vs_single_speedup"] = _safe_ratio(sec_s, sec_r)
    g = ring_attention_geometry(L, ways, B=B, H=H, D=D)
    out["roofline_ring_ici"] = _roofline(
        g["ring_total_ici_bytes_per_chip"],
        g["ring_total_ici_bytes_per_chip"], sec_r)
    return out


def bench_ring_attention():
    """Sequence-parallel ring attention evidence (ISSUE 17).

    The ``geometry`` rows are pure arithmetic — bytes-over-ICI per ring
    step vs the allgather burst, and per-chip peak K/V residency
    O(L/ways) vs O(L) — at the 8k/32k/128k contexts the workload
    opens; deterministic, so the doc of record pins them.  The measured
    leg (ring vs single-chip blockwise at a CPU-sized shape) runs in a
    subprocess with a forced 8-device CPU mesh (``measured.device``
    says so): the geometry is identical on real silicon, the measured
    ratio is a CPU ratio.  On TPU a breached speedup floor captures a flight record
    + device profiler trace under BENCH_PROFILE_DIR/ring_attention.
    """
    import subprocess
    import sys

    import jax

    WAYS = 4
    out = {"geometry": {
        f"l{L}": ring_attention_geometry(L, WAYS)
        for L in (8192, 32768, 131072)}}
    out["geometry"]["ways"] = WAYS
    code = (
        "import os;"
        "os.environ['JAX_PLATFORMS']='cpu';"
        "os.environ['XLA_FLAGS']=os.environ.get('XLA_FLAGS','')"
        "+' --xla_force_host_platform_device_count=8';"
        "import sys, json; sys.path.insert(0, os.getcwd());"
        "from bench import bench_ring_attention_child;"
        "from analytics_zoo_tpu.core.context import describe_devices;"
        "print('RINGJSON', json.dumps(dict(bench_ring_attention_child(),"
        " device=describe_devices())))")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=max(60, min(300, _remaining() - 20)),
            cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in proc.stdout.splitlines():
            if line.startswith("RINGJSON "):
                out["measured"] = json.loads(line[len("RINGJSON "):])
                break
        else:
            out["child_error"] = (f"child rc={proc.returncode}: "
                                  f"{(proc.stderr or '')[-400:]}")
    except Exception as e:
        out["child_error"] = f"{type(e).__name__}: {e}"
    spd = (out.get("measured") or {}).get("ring_vs_single_speedup")
    if spd is not None:
        out["ring_vs_single_speedup"] = spd
    if jax.default_backend() == "tpu":
        # the speedup floor only binds where real ICI links exist — a
        # breach ships its own device trace next to the artifact
        _breach_check(out, "ring_attention", "ring_vs_single_speedup",
                      1.0)
    return out


def bench_dequant_matmul(device, m=1024, n=4096, K=32, rounds=2):
    """Fused dequantize-matmul (int8 / packed-int4 weight storage) vs
    the f32 matmul: the serving-replica HBM-footprint claim.  The
    weight-bytes rows are exact (storage is deterministic); the parity
    rows quote relative error plus top-1 stability over the m output
    rows, the ranking-model acceptance criterion."""
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.dequant_matmul import (
        dequant_matmul, quantize_weights)

    k = n       # square weight so the scan carry re-feeds the output
    rs = np.random.RandomState(0)
    x = jax.device_put(jnp.asarray(
        rs.randn(m, k).astype(np.float32)), device)
    w = rs.randn(k, n).astype(np.float32) * 0.1
    q8, s8 = quantize_weights(w, bits=8)
    q4, s4 = quantize_weights(w, bits=4)
    wd = jax.device_put(jnp.asarray(w), device)
    q8, s8, q4, s4 = (jax.device_put(a, device)
                      for a in (q8, s8, q4, s4))

    out = {"shape": {"m": m, "k": k, "n": n},
           "weight_bytes_f32": k * n * 4,
           "weight_bytes_int8": int(q8.size),
           "weight_bytes_int4": int(q4.size)}
    out["weight_hbm_ratio_int8"] = _safe_ratio(q8.size, k * n * 4)
    out["weight_hbm_ratio_int4"] = _safe_ratio(q4.size, k * n * 4, nd=3)

    yf = np.asarray(jax.jit(lambda a: a @ wd)(x))
    for bits, q, s in ((8, q8, s8), (4, q4, s4)):
        yq = np.asarray(jax.jit(
            lambda a, q=q, s=s, b=bits: dequant_matmul(
                a, q, s, bits=b, rows=k))(x))
        rel = float(np.linalg.norm(yq - yf) / np.linalg.norm(yf))
        out[f"rel_err_int{bits}"] = round(rel, 5)
        out[f"top1_match_int{bits}"] = round(float(
            (yq.argmax(-1) == yf.argmax(-1)).mean()), 4)

    progs = {
        "f32_ms": _make_scan_program(lambda c: c @ wd),
        "int8_ms": _make_scan_program(
            lambda c: dequant_matmul(c, q8, s8)),
        "int4_ms": _make_scan_program(
            lambda c: dequant_matmul(c, q4, s4, bits=4, rows=k)),
    }
    errs = _warm_parallel([(p, x) for p in progs.values()], threads=3)
    for idx, (key, many) in enumerate(progs.items()):
        if idx in errs:
            out[key.replace("_ms", "_error")] = type(errs[idx]).__name__
            continue
        ms = _measure_scan(many, x, K, rounds=rounds, probe=False)
        if ms is None:
            out[key] = None
            out[key.replace("_ms", "_unresolved")] = \
                "slope below timer resolution after escalation"
        else:
            out[key] = round(ms, 3)
    for bits in (8, 4):
        out[f"int{bits}_vs_f32_speedup"] = _safe_ratio(
            out.get("f32_ms"), out.get(f"int{bits}_ms"))
    # per-leg compulsory traffic: activations in/out + that leg's own
    # weight storage, read once (the fused kernel achieves it — the
    # dequant never materialises a f32 weight in HBM)
    io = 4 * (m * k + m * n)
    for key, wb in (("f32", k * n * 4), ("int8", int(q8.size)),
                    ("int4", int(q4.size))):
        ms = out.get(f"{key}_ms")
        out[f"roofline_{key}"] = _roofline(io + wb, io + wb,
                                           ms * 1e-3 if ms else None)
    return out


# ---------------------------------------------------------------------------
# Serving: InferenceModel latency/throughput (BASELINE config #5 evidence;
# the reference's Cluster Serving publishes only a "Serving Throughput"
# scalar, wp-bigdl/ClusterServingGuide — here are real numbers)
# ---------------------------------------------------------------------------

def bench_serving(n_requests=32, concurrency=8, n_saturated=256):
    import threading

    from analytics_zoo_tpu.core.profiling import TIMERS
    from analytics_zoo_tpu.deploy import (
        ClusterServing, DynamicBatcher, InferenceModel, InputQueue,
        MemoryQueue, OutputQueue, ServingConfig)
    from analytics_zoo_tpu.loadgen.payloads import saturated_images
    from analytics_zoo_tpu.models.image.imageclassification import mobilenet
    from analytics_zoo_tpu.nn import reset_name_scope

    # mobilenet: a real conv net with serving-relevant shape but ~4x
    # cheaper XLA compiles than resnet50 (two buckets = two compiles
    # per forward flavor, and the driver's bench window is finite)
    reset_name_scope()
    net = mobilenet(class_num=1000)
    import jax

    from analytics_zoo_tpu.deploy import imagenet_preprocess

    params, state = net.init(jax.random.PRNGKey(0))
    # uint8 wire format: clients ship raw bytes, the chip normalizes
    # in-program — 4x fewer host→device bytes than float32
    m = InferenceModel.from_keras_net(net, params, state,
                                      preprocess=imagenet_preprocess(),
                                      batch_buckets=(1, 32))
    rs = np.random.RandomState(0)
    # a distinct image per request, as real clients send
    imgs = [rs.randint(0, 256, (1, 224, 224, 3)).astype(np.uint8)
            for _ in range(12)]
    img = imgs[0]

    # warm BOTH shape buckets concurrently (threaded XLA compile)
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(2) as ex:
        futs = [ex.submit(m.predict, [img]),
                ex.submit(m.predict, [np.repeat(img, 32, axis=0)])]
        for f in futs:
            f.result()

    out = {"wire_format": "uint8+on-device normalize"}

    # --- sync baseline (the pre-pipeline engine, kept so the speedup is
    # measured in-repo: blocking predict per batch, no stage overlap) ---
    sync = {}
    lats = []
    for i in range(10):
        t0 = time.perf_counter()
        m.predict([imgs[1 + (i % 11)]])
        lats.append((time.perf_counter() - t0) * 1e3)
    lats.sort()
    sync["latency_p50_ms"] = round(lats[len(lats) // 2], 2)
    sync["latency_p99_ms"] = round(lats[-1], 2)

    batcher = DynamicBatcher(m, max_batch=32, max_latency_ms=5.0)
    try:
        batcher.predict([img])                     # bucket 32 pre-warmed
        done = []
        lock = threading.Lock()

        def client(k):
            crs = np.random.RandomState(100 + k)
            for _ in range(n_requests // concurrency):
                fresh = crs.randint(0, 256, (1, 224, 224, 3)).astype(
                    np.uint8)
                r = batcher.predict([fresh])
                with lock:
                    done.append(r)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(concurrency)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        sync["batched_throughput_imgs_per_sec"] = round(len(done) / dt, 1)
        sync["concurrency"] = concurrency
    finally:
        batcher.close()
    out["serving_sync_baseline"] = sync

    # --- pipelined engine: the full queue path (enqueue → poller →
    # decode pool → DynamicBatcher → DeviceExecutor → respond pool) ---
    q = MemoryQueue()
    srv = ClusterServing(m, q, ServingConfig(
        batch_size=32, poll_timeout_s=0.01, max_batch_delay_ms=5.0,
        decode_workers=4, max_inflight=2)).start()
    inp, outp = InputQueue(q), OutputQueue(q)
    try:
        # warm the replica forward's two bucket programs (a fresh jitted
        # fn: params are explicit args so replicas can live per device)
        inp.enqueue(uri="warm1", x=imgs[1][0])
        outp.query("warm1", timeout=600.0)
        for i in range(32):
            inp.enqueue(uri=f"warm32_{i}", x=imgs[2 + i % 10][0])
        for i in range(32):
            outp.query(f"warm32_{i}", timeout=600.0)

        # trickle latency: sequential single requests, full queue path
        # (deadline flush + device + codec — what one user experiences)
        lats = []
        crs = np.random.RandomState(7)
        for i in range(10):
            fresh = crs.randint(0, 256, (224, 224, 3)).astype(np.uint8)
            t0 = time.perf_counter()
            inp.enqueue(uri=f"lat{i}", x=fresh)
            outp.query(f"lat{i}", timeout=120.0)
            lats.append((time.perf_counter() - t0) * 1e3)
        lats.sort()
        out["latency_p50_ms"] = round(lats[len(lats) // 2], 2)
        out["latency_p99_ms"] = round(lats[-1], 2)

        # saturated offered load: every request pre-enqueued (queue depth
        # >> batch) with a DISTINCT image, so the executor sees back-to-
        # back full batches and the decode pool overlaps device compute.
        # Timers reset first: the breakdown must attribute the steady
        # state, not warmup compiles.
        TIMERS.reset()
        sat = saturated_images(n_saturated, rs=crs)
        t0 = time.perf_counter()
        for i, im in enumerate(sat):
            inp.enqueue(uri=f"sat{i}", x=im)
        served = 0
        deadline = time.monotonic() + 600
        while served < n_saturated and time.monotonic() < deadline:
            served += len(outp.dequeue(timeout=1.0))
        dt = time.perf_counter() - t0
        out["batched_throughput_imgs_per_sec"] = round(served / dt, 1)
        out["saturated_requests"] = served

        # per-stage latency attribution + overlap counters (the same
        # rollups health() serves)
        breakdown = {}
        for k, v in TIMERS.stats().items():
            if k.startswith("serving/") and v["count"]:
                breakdown[k.split("/", 1)[1]] = {
                    "p50_ms": round(v["p50_s"] * 1e3, 2),
                    "p99_ms": round(v["p99_s"] * 1e3, 2)}
        out["stage_breakdown"] = breakdown
        out["pipeline_counters"] = {
            k.split("/", 1)[1]: n for k, n in TIMERS.counts().items()
            if k.startswith("serving/")}

        # where each served image's time actually went: device compute
        # vs wire/codec (decode+respond pools) vs queueing (stream wait
        # + batcher wait).  Stage totals sum across worker threads and
        # in-flight batches, so per-image numbers can exceed wall/served
        # and busy fractions can exceed 1.0 — that overlap is the
        # pipelining being measured, not an accounting bug.
        # Chaos injection is OFF here (no FaultInjector armed): this is
        # the fault-free baseline the serving acceptance bound tracks.
        stats = TIMERS.stats()
        tot = lambda nm: stats.get(nm, {}).get("total_s", 0.0)
        if served:
            per_img = lambda s: round(s * 1e3 / served, 3)
            wire_s = tot("serving/decode") + tot("serving/respond")
            queue_s = tot("serving/queue_wait") + tot("serving/batch_wait")
            out["breakdown"] = {
                "device_compute_ms_per_img": per_img(tot("serving/device")),
                "wire_codec_ms_per_img": per_img(wire_s),
                "queue_wait_ms_per_img": per_img(queue_s),
                "device_busy_frac": round(tot("serving/device") / dt, 3),
                "decode_busy_frac": round(tot("serving/decode") / dt, 3),
                "respond_busy_frac": round(tot("serving/respond") / dt, 3),
                "chaos_enabled": False,
            }
        out["speedup_vs_sync"] = _safe_ratio(
            out["batched_throughput_imgs_per_sec"],
            sync.get("batched_throughput_imgs_per_sec"))
    finally:
        srv.stop()

    # --- zero-copy shm leg: same model, same saturated pattern, the
    # shared-memory ring + binary wire instead of MemoryQueue + base64.
    # Own TIMERS window so the breakdown attributes this leg alone. ---
    from analytics_zoo_tpu.deploy.shmqueue import ShmQueue, shm_available

    if shm_available():
        q2 = ShmQueue(name="bench_serving", slots=max(64, n_saturated),
                      slot_bytes=1 << 20, push_timeout_s=30.0)
        srv2 = ClusterServing(m, q2, ServingConfig(
            batch_size=32, poll_timeout_s=0.01, max_batch_delay_ms=5.0,
            decode_workers=4, max_inflight=2)).start()
        inp2, outp2 = InputQueue(q2), OutputQueue(q2)
        try:
            inp2.enqueue(uri="warm1", x=imgs[1][0])
            outp2.query("warm1", timeout=600.0)
            TIMERS.reset()
            crs = np.random.RandomState(11)
            sat = saturated_images(n_saturated, rs=crs)
            t0 = time.perf_counter()
            for i, im in enumerate(sat):
                inp2.enqueue(uri=f"shm{i}", x=im)
            served = 0
            deadline = time.monotonic() + 600
            while served < n_saturated and time.monotonic() < deadline:
                served += len(outp2.dequeue(timeout=1.0))
            dt = time.perf_counter() - t0
            stats = TIMERS.stats()
            tot = lambda nm: stats.get(nm, {}).get("total_s", 0.0)
            counts = TIMERS.counts()
            shm_out = {
                "batched_throughput_imgs_per_sec": round(served / dt, 1),
                "saturated_requests": served,
                "wire_format": "shm ring + binary frames (zero-copy)",
            }
            if served:
                per_img = lambda s: round(s * 1e3 / served, 3)
                shm_out["breakdown"] = {
                    "device_compute_ms_per_img": per_img(
                        tot("serving/device")),
                    "wire_codec_ms_per_img": per_img(
                        tot("serving/decode") + tot("serving/respond")),
                    "queue_wait_ms_per_img": per_img(
                        tot("serving/queue_wait")
                        + tot("serving/batch_wait")),
                    "chaos_enabled": False,
                }
                # the zero-copy claim, re-verified at bench time
                shm_out["codec_b64_calls"] = (
                    counts.get("serving/codec_b64_encode", 0)
                    + counts.get("serving/codec_b64_decode", 0))
            out["serving_shm"] = shm_out
            out["shm_speedup_vs_memory_queue"] = _safe_ratio(
                shm_out["batched_throughput_imgs_per_sec"],
                out.get("batched_throughput_imgs_per_sec"))
        finally:
            srv2.stop()
            q2.stop()
    else:
        out["serving_shm"] = {"skipped": SKIP_SHM}
    return out


def bench_serving_wire_codecs(n_codec=64, n_queue=256):
    """The wire tax, isolated (docs/PERFORMANCE.md "Serving wire
    codecs"): how fast tensor payloads cross each serving wire, with the
    device and pipeline machinery factored out.

    Two tiers:
    - codec micro: encode+decode of one uint8 image record per codec —
      the legacy json+base64 envelope, the binary frame, and the binary
      frame through an actual shm slot (pack into the segment, decode a
      zero-copy view back out).
    - queue path: producer -> queue -> worker-side decode ->
      jax.device_put, per record, same run: the legacy serialized json
      wire (what File/Redis ship), the in-process MemoryQueue shortcut
      (dict hand-off, base64 tensors), and the ShmQueue binary ring.
      ``queue_path_speedup`` = shm vs the serialized json wire — the
      end-to-end zero-copy win.
    """
    import gc
    import json as _json

    import jax

    from analytics_zoo_tpu.core.profiling import TIMERS
    from analytics_zoo_tpu.deploy import (MemoryQueue, encode_tensor,
                                          pack_record, unpack_record)
    from analytics_zoo_tpu.deploy.serving import _decode_record
    from analytics_zoo_tpu.deploy.shmqueue import ShmQueue, shm_available

    rs = np.random.RandomState(0)
    img = rs.randint(0, 256, (224, 224, 3)).astype(np.uint8)
    nbytes = img.nbytes
    out = {"payload": "uint8 224x224x3", "payload_bytes": nbytes}
    mbs = lambda n, dt: round(n * nbytes / dt / 1e6, 1)

    # --- tier 1: raw codec round-trips --------------------------------
    def rec_of(i):
        return {"uri": f"c{i}", "ts": 0.0, "fmt": "tensor", "x": img}

    t0 = time.perf_counter()
    for i in range(n_codec):
        blob = _json.dumps({**rec_of(i), "x": encode_tensor(img)})
        back = _json.loads(blob)
        _decode_record(back)
    dt_json = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(n_codec):
        _decode_record(unpack_record(pack_record(rec_of(i))))
    dt_bin = time.perf_counter() - t0
    micro = {
        "json_b64_imgs_per_sec": round(n_codec / dt_json, 1),
        "json_b64_mb_per_sec": mbs(n_codec, dt_json),
        "binary_imgs_per_sec": round(n_codec / dt_bin, 1),
        "binary_mb_per_sec": mbs(n_codec, dt_bin),
        "binary_speedup": _safe_ratio(n_codec / dt_bin,
                                      n_codec / dt_json),
    }
    if shm_available():
        q = ShmQueue(name="codec_micro", slots=8,
                     slot_bytes=nbytes + (1 << 12))
        try:
            t0 = time.perf_counter()
            for i in range(n_codec):
                q.push(rec_of(i))
                [(_, rec)] = q.pop_batch(1, timeout=1.0)
                _decode_record(rec)
                del rec         # release the slot lease
            dt_shm = time.perf_counter() - t0
            micro["shm_imgs_per_sec"] = round(n_codec / dt_shm, 1)
            micro["shm_mb_per_sec"] = mbs(n_codec, dt_shm)
            micro["shm_speedup"] = _safe_ratio(n_codec / dt_shm,
                                               n_codec / dt_json)
        finally:
            q.stop()
    out["codec_micro"] = micro

    # --- tier 2: through the queue to the device ----------------------
    # Two payload sizes: the uint8 image wire (150KB — shm fixed costs
    # show) and the float32 tensor wire (600KB — the regime embeddings /
    # feature tensors live in, where the per-byte codec tax dominates).
    jax.device_put(img).block_until_ready()     # backend warmup

    def queue_leg(push_one, pop_decode, n, chunk=32):
        """push `chunk` records, pop + decode + device_put them, repeat;
        returns imgs/s.  Per-record device_put on both sides keeps the
        comparison honest (the device share is identical)."""
        t0 = time.perf_counter()
        done = 0
        while done < n:
            k = min(chunk, n - done)
            for i in range(k):
                push_one(done + i)
            popped = pop_decode(k)
            assert len(popped) == k
            for x in popped:
                jax.device_put(x).block_until_ready()
            done += k
            del popped, x
        return n / (time.perf_counter() - t0)

    out["queue_path"] = {}
    for dtype_name, a in (("uint8", img),
                          ("float32", img.astype(np.float32))):
        pb = a.nbytes
        pmbs = lambda rate: round(rate * pb / 1e6, 1)

        def rec_a(i):
            return {"uri": f"q{i}", "ts": 0.0, "fmt": "tensor", "x": a}

        qp = {"payload_bytes": pb}
        # legacy serialized wire: json envelope + base64 tensors (the
        # File/Redis legacy shape, writable-copy decode semantics),
        # transported over MemoryQueue so only the codec differs
        qj = MemoryQueue()
        rate = queue_leg(
            lambda i: qj.push(_json.loads(_json.dumps(
                {**rec_a(i), "x": encode_tensor(a)}))),
            lambda k: [_decode_record(r)["x"]
                       for _, r in qj.pop_batch(k, timeout=1.0)],
            n_queue)
        qp["json_wire_imgs_per_sec"] = round(rate, 1)
        qp["json_wire_mb_per_sec"] = pmbs(rate)
        # in-process shortcut: same base64 tensor payloads, no envelope
        qm = MemoryQueue()
        rate = queue_leg(
            lambda i: qm.push({**rec_a(i), "x": encode_tensor(a)}),
            lambda k: [_decode_record(r)["x"]
                       for _, r in qm.pop_batch(k, timeout=1.0)],
            n_queue)
        qp["memory_b64_imgs_per_sec"] = round(rate, 1)
        if shm_available():
            qs = ShmQueue(name="codec_path", slots=64,
                          slot_bytes=pb + (1 << 12), push_timeout_s=10.0)
            try:
                c0 = TIMERS.counts()
                rate = queue_leg(
                    lambda i: qs.push(rec_a(i)),
                    lambda k: [_decode_record(r)["x"]
                               for _, r in qs.pop_batch(k, timeout=1.0)],
                    n_queue)
                gc.collect()
                counts = TIMERS.counts()
                qp["shm_imgs_per_sec"] = round(rate, 1)
                qp["shm_mb_per_sec"] = pmbs(rate)
                # counter-verified zero-copy at bench time
                qp["shm_tensor_copies"] = (
                    counts.get("serving/codec_tensor_copies", 0)
                    - c0.get("serving/codec_tensor_copies", 0))
                qp["queue_path_speedup"] = _safe_ratio(
                    qp["shm_imgs_per_sec"],
                    qp["json_wire_imgs_per_sec"])
            finally:
                qs.stop()
        else:
            _skip(qp, "shm", SKIP_SHM)
        out["queue_path"][dtype_name] = qp
    return out


def bench_restart_to_slo_child(cache_dir, buckets=(1, 8, 32),
                               slo_ms=200.0, n_probe=12):
    """One process leg of the restart-to-SLO bench — run in a fresh
    subprocess so the in-process jit caches can't leak between the cold
    and warm legs.  The on-disk state of ``cache_dir`` is the only
    thing distinguishing them: empty = cold (every bucket pays a live
    XLA compile), populated = warm restart (``warm()`` pre-installs the
    persisted executables; docs/SERVING.md "Warm start & multi-model").

    Two clocks, both from model-ready (pipeline/queue overhead
    excluded — this times the replica forward path itself):

    - ``coverage_s`` — until every ``batch_buckets`` program has served
      a batch (full bucket coverage);
    - ``slo_s`` — until a probe request's p99 (sliding window over the
      last 10 probes, round-robin across buckets) first drops under
      ``slo_ms``.  Compiles land inside early probes, so the cold leg
      crosses the SLO line only after paying them.
    """
    import numpy as np

    from analytics_zoo_tpu.deploy import CompileCache, InferenceModel
    from analytics_zoo_tpu.nn import Sequential, reset_name_scope
    from analytics_zoo_tpu.nn.layers.core import Activation, Dense
    from analytics_zoo_tpu.train.optimizers import Adam

    in_dim, out_dim = 12, 4
    rs = np.random.RandomState(0)
    reset_name_scope()
    net = Sequential([Dense(64, input_shape=(in_dim,)), Activation("relu"),
                      Dense(out_dim)])
    net.compile(optimizer=Adam(1e-2), loss="mse")
    x = rs.randn(max(buckets), in_dim).astype(np.float32)
    net.fit(x, rs.randn(max(buckets), out_dim).astype(np.float32),
            batch_size=16, nb_epoch=1, verbose=False)
    m = InferenceModel.from_keras_net(net, net.estimator.params,
                                      net.estimator.state,
                                      batch_buckets=tuple(buckets))
    cache = CompileCache(cache_dir)
    m.attach_compile_cache(cache)

    t_start = time.monotonic()
    warmed = m.warm()
    for b in buckets:
        m.predict(x[:b])
    coverage_s = time.monotonic() - t_start

    lats = []
    slo_s = None
    for i in range(n_probe):
        b = buckets[i % len(buckets)]
        t0 = time.monotonic()
        m.predict(x[:b])
        lats.append((time.monotonic() - t0) * 1e3)
        win = sorted(lats[-10:])
        if slo_s is None and win[-1] <= slo_ms:
            slo_s = time.monotonic() - t_start
    return {"warmed": int(warmed),
            "compile_count": int(m.compile_count),
            "coverage_s": round(coverage_s, 3),
            "slo_s": round(slo_s, 3) if slo_s is not None else None,
            "probe_p99_ms": round(sorted(lats)[-1], 3),
            "cache_events": dict(cache.stats()["events"])}


def bench_serving_restart_to_slo(slo_ms=200.0):
    """Warm-start restart bench (ISSUE 15 acceptance): a cold process
    vs a restarted process over the same persistent compile-cache dir,
    each leg a REAL fresh OS process (``bench_restart_to_slo_child``).
    The honest claims: the warm leg performs ZERO live XLA compiles
    (counter-proven by ``compile_count``) and reaches full bucket
    coverage ≥ 5x faster than the cold leg.  Forced-CPU children, like
    the dlrm leg: compile cost is what's being measured and the warm/
    cold *ratio* is the claim, so the host CPU backend stands in; the
    jax persistent compilation cache is NOT enabled in the children
    (that would hide exactly the cost this bench measures).
    """
    import shutil
    import subprocess
    import sys
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="zoo_bench_xc_")
    out = {"slo_ms": slo_ms, "buckets": [1, 8, 32]}
    code = (
        "import os;"
        "os.environ['JAX_PLATFORMS']='cpu';"
        "import sys, json; sys.path.insert(0, os.getcwd());"
        "from bench import bench_restart_to_slo_child;"
        "from analytics_zoo_tpu.core.context import describe_devices;"
        f"print('XCJSON', json.dumps(dict(bench_restart_to_slo_child("
        f"{cache_dir!r}, slo_ms={slo_ms}), device=describe_devices())))")
    try:
        for leg in ("cold", "warm"):
            proc = subprocess.run(
                [sys.executable, "-c", code], capture_output=True,
                text=True, timeout=max(60, min(300, _remaining() - 20)),
                cwd=os.path.dirname(os.path.abspath(__file__)))
            for line in proc.stdout.splitlines():
                if line.startswith("XCJSON "):
                    out[leg] = json.loads(line[len("XCJSON "):])
                    break
            else:
                out[f"{leg}_error"] = (f"child rc={proc.returncode}: "
                                       f"{(proc.stderr or '')[-400:]}")
                return out
        out["warm_live_compiles"] = out["warm"]["compile_count"]
        out["coverage_speedup_warm_vs_cold"] = _safe_ratio(
            out["cold"]["coverage_s"], out["warm"]["coverage_s"])
        out["slo_speedup_warm_vs_cold"] = _safe_ratio(
            out["cold"]["slo_s"], out["warm"]["slo_s"])
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return out


def _run_metadata(device):
    """Provenance stamp for BENCH_*.json artifacts: which commit, which
    jax, which silicon produced the numbers."""
    import subprocess

    import jax

    meta = {"timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "jax_version": jax.__version__,
            "device_kind": device.device_kind,
            "platform": device.platform,
            "device_count": len(jax.devices())}
    try:        # the chip machine's copy of the repo is not a git checkout
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        sha = ""
    if sha:
        meta["git_sha"] = sha
    return meta


def main():
    import sys

    import jax

    from analytics_zoo_tpu.core.context import enable_compile_cache

    enable_compile_cache()
    accel = jax.devices()[0]
    if accel.platform != "tpu":
        # a measurement path that finds no chip fails: no JSON line, no
        # per-chip metric
        print(f"bench.py measures a TPU; jax found "
              f"{accel.platform}:{accel.device_kind}", file=sys.stderr)
        sys.exit(1)
    extra = {}
    extra["run_metadata"] = _run_metadata(accel)
    section_s = {}
    extra["section_seconds"] = section_s
    report = {"metric": "ncf_movielens1m_train_samples_per_sec_per_chip",
              "value": 0.0, "unit": "samples/sec/chip",
              "vs_baseline": None, "extra": extra}
    watchdog = _Watchdog(report)

    def _mark(name, t0):
        section_s[name] = round(time.time() - t0, 1)
        print(f"[bench] {name}: {section_s[name]}s "
              f"(elapsed {time.time() - _T0:.0f}s of {_BUDGET_S:.0f})",
              file=sys.stderr, flush=True)

    # --- ORDERING (r4 verdict #1 + r5 measured compile bills): every
    # section the r4 artifact dropped runs in the first ~250s (int8,
    # serving, WND, nnframes, then the headline), the accuracy legs
    # (convergence, resnet, resnet_accuracy) take the middle, and
    # attention — whose 6 kernel compiles were the single largest bill
    # in r5 — closes with per-length guards.  The watchdog guarantees
    # the JSON line regardless.

    # int8 MXU matmul vs f32 (the int8 inference claim)
    t0 = time.time()
    try:
        extra["matmul_4096"] = bench_int8(accel)
    except Exception as e:
        extra["int8_error"] = f"{type(e).__name__}: {e}"
    _mark("int8", t0)

    # ops/ fused kernels (PR 12): embedding-bag and dequant-matmul vs
    # their unfused XLA lowerings, roofline bytes rows alongside
    t0 = time.time()
    if _remaining() > 60:
        try:
            extra["embedding_bag"] = bench_embedding_bag(accel)
        except Exception as e:
            extra["embedding_bag_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "embedding_bag")
    _mark("embedding_bag", t0)

    t0 = time.time()
    if _remaining() > 60:
        try:
            extra["dequant_matmul"] = bench_dequant_matmul(accel)
        except Exception as e:
            extra["dequant_matmul_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "dequant_matmul")
    _mark("dequant_matmul", t0)

    # BASELINE config #5: serving latency + batched throughput
    t0 = time.time()
    try:
        extra["serving_mobilenet"] = bench_serving()
    except Exception as e:
        extra["serving_error"] = f"{type(e).__name__}: {e}"
    _mark("serving", t0)

    # serving wire codecs: the isolated wire tax (json+b64 vs binary vs
    # shm ring), device/pipeline factored out — runs on host, no accel
    t0 = time.time()
    try:
        extra["serving_wire_codecs"] = bench_serving_wire_codecs()
    except Exception as e:
        extra["serving_wire_codecs_error"] = f"{type(e).__name__}: {e}"
    _mark("serving_wire_codecs", t0)

    # restart-to-SLO: persistent compile cache, cold vs warm restart
    # (fresh forced-CPU subprocess per leg — host-side, no accel)
    t0 = time.time()
    if _remaining() > 90:
        try:
            extra["serving_restart_to_slo"] = bench_serving_restart_to_slo()
        except Exception as e:
            extra["serving_restart_to_slo_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "serving_restart_to_slo")
    _mark("serving_restart_to_slo", t0)

    # BASELINE config #4: WideAndDeep throughput
    t0 = time.time()
    try:
        extra["wide_and_deep_samples_per_sec"] = round(
            bench_wide_and_deep(accel), 1)
    except Exception as e:
        extra["wide_and_deep_error"] = f"{type(e).__name__}: {e}"
    _mark("wide_and_deep", t0)

    # BASELINE config #3: NNFrames DataFrame pipeline rows/sec
    t0 = time.time()
    try:
        extra["nnframes"] = bench_nnframes()
    except Exception as e:
        extra["nnframes_error"] = f"{type(e).__name__}: {e}"
    _mark("nnframes", t0)

    # headline: NCF throughput, bf16 (MXU) with f32 quoted alongside.
    # batch/k chosen by on-chip sweep (65536x128 fused: 19M vs 8.2M at
    # 8192x64 — per-op dispatch overhead amortizes with scale)
    t0 = time.time()
    hb, hk = 65536, 128
    extra["headline_config"] = {"batch": hb, "k_steps": hk}
    value_f32 = bench_ncf(accel, batch=hb, k_steps=hk, iters=2)
    extra["ncf_f32_samples_per_sec"] = round(value_f32, 1)
    value_bf16 = bench_ncf(accel, batch=hb, k_steps=hk, iters=2,
                           compute_dtype="bfloat16")
    extra["ncf_bf16_samples_per_sec"] = round(value_bf16, 1)
    value = max(value_bf16, value_f32)
    extra["dtype"] = "bfloat16" if value_bf16 >= value_f32 else "float32"
    report["value"] = round(value, 1)    # watchdog snapshot carries it
    _mark("ncf_headline", t0)

    vs_baseline = None
    t0 = time.time()
    try:
        # k_steps=8 keeps the baseline cheap; throughput is per-sample
        # normalized so vs_baseline stays comparable
        cpu = jax.local_devices(backend="cpu")[0]
        cpu_tput = (bench_ncf(cpu, warmup=1, iters=2, k_steps=8)
                    if _remaining() > 60 else 0)
        if cpu_tput > 0:
            vs_baseline = value / cpu_tput
            extra["cpu_baseline_samples_per_sec"] = round(cpu_tput, 1)
            report["vs_baseline"] = round(vs_baseline, 3)
    except Exception as e:
        extra["cpu_baseline_error"] = f"{type(e).__name__}: {e}"
    _mark("cpu_baseline", t0)

    # tentpole evidence: host-prefetch vs HBM-resident FeatureSet through
    # the SAME Estimator.fit — both end-to-end data paths, NCF- and
    # WND-shaped (the gap the resident path exists to close)
    t0 = time.time()
    if _remaining() > 150:
        try:
            extra["featureset_data_paths"] = bench_data_paths(
                n_rows=1 << 20, epochs=3)
        except Exception as e:
            extra["data_paths_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "data_paths")
    _mark("data_paths", t0)

    # streaming tier evidence (ISSUE 10): a dataset 4x the device budget
    # rotating through HBM vs whole-dataset residency — the ≥0.5x floor
    # plus the overlap-fraction counter-proof
    t0 = time.time()
    if _remaining() > 120:
        try:
            extra["featureset_streaming"] = bench_featureset_streaming(
                n_rows=1 << 20, epochs=3)
        except Exception as e:
            extra["featureset_streaming_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "featureset_streaming")
    _mark("featureset_streaming", t0)

    # sharded giant-embedding evidence (ISSUE 14): per-chip table HBM
    # = replicated/ways + psum-exchange geometry (analytic, pinned in
    # docs/PERFORMANCE.md), plus measured parity/train/10⁸-row-lookup
    # legs on a subprocess dryrun dp×tp mesh
    t0 = time.time()
    if _remaining() > 150:
        try:
            extra["dlrm_sharded_embedding"] = bench_dlrm_sharded(
                giant=_remaining() > 240)
        except Exception as e:
            extra["dlrm_sharded_embedding_error"] = \
                f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "dlrm_sharded_embedding")
    _mark("dlrm_sharded_embedding", t0)

    # hot-row cache + dedup for sharded lookups (ISSUE 19): zipfian
    # exchange/HBM bytes-moved geometry (deterministic, ≥5× gate at
    # s=1.0 pinned in docs/PERFORMANCE.md) plus measured cached-vs-
    # uncached and dedup-vs-naive parity on a subprocess dryrun mesh
    t0 = time.time()
    if _remaining() > 120:
        try:
            res = bench_table_hot_cache()
            extra["table_hot_cache"] = res
            geo = res.get("geometry")
            if isinstance(geo, dict):
                _breach_check(geo, "table_hot_cache",
                              "exchange_reduction_ideal", 5.0)
        except Exception as e:
            extra["table_hot_cache_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "table_hot_cache")
    _mark("table_hot_cache", t0)

    # sequence-parallel ring attention (ISSUE 17): analytic
    # bytes-over-ICI + peak-residency geometry at 8k/32k/128k (pinned
    # in docs/PERFORMANCE.md) and a measured ring-vs-single-chip leg on
    # a subprocess 8-device mesh
    t0 = time.time()
    if _remaining() > 90:
        try:
            extra["ring_attention"] = bench_ring_attention()
        except Exception as e:
            extra["ring_attention_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "ring_attention")
    _mark("ring_attention", t0)

    # durability layer cost (ISSUE 3): verified-checkpoint overhead on
    # the training path — async should be ~free, sync bounds the worst
    # case (the preemption-flush latency)
    t0 = time.time()
    if _remaining() > 120:
        try:
            extra["checkpoint_overhead"] = bench_checkpoint_overhead()
        except Exception as e:
            extra["checkpoint_overhead_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "checkpoint_overhead")
    _mark("checkpoint_overhead", t0)

    # north-star evidence in ONE run: matched-accuracy convergence with
    # device-resident data + the CPU leg of the SAME code path — the
    # BASELINE.json headline evidence, so it runs before everything
    # whose compile bill could crowd it out.  Depth adapts: the 2-seed
    # score ensemble buys ~+0.4 HR@10 points (r4: 0.929 at 2x8 vs
    # 0.9255 single-12) and runs when earlier sections underran.
    t0 = time.time()
    if _remaining() > 100:
        try:
            if _remaining() > 500:
                ens, ep = 2, 8
            else:
                ens, ep = 1, (12 if _remaining() > 140 else 8)
            extra["ncf_convergence"] = bench_ncf_convergence(
                epochs=ep, ensemble=ens,
                cpu_baseline_epochs=2)
        except Exception as e:
            extra["ncf_convergence_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "ncf_convergence")
    _mark("ncf_convergence", t0)

    # BASELINE config #2: ResNet-50 imgs/sec — one launch-amortized
    # measurement (see bench_resnet50).
    # Primary leg = ghost-BN stats_fraction=0.25 (the r4 verdict's BN
    # bandwidth-wall attack: quarter-batch statistics cut the stats-pass
    # HBM traffic; accuracy parity in tests/test_ghost_bn.py) — r5
    # on-silicon: 2539 imgs/s vs 2433 full-BN (and 2743 at frac=0.125).
    t0 = time.time()
    if _remaining() > 90:
        try:
            # variant-explicit key (ADVICE r5): the ghost-BN number can
            # no longer masquerade as the full-BN headline across rounds
            tput = round(bench_resnet50(accel, bn_stats_fraction=0.25), 2)
            extra["resnet50_ghostbn025_imgs_per_sec"] = tput
            extra["resnet50_bn_stats_fraction"] = 0.25
            extra["resnet50_method"] = ("4/8-step fori slope, uint8 feed "
                                        "(launch-amortized; no superbatch)")
            if _remaining() > 150:      # full-BN alongside, so cross-round
                extra["resnet50_imgs_per_sec_per_chip"] = round(  # compares
                    bench_resnet50(accel, bn_stats_fraction=1.0), 2)
        except Exception as e:
            extra["resnet50_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "resnet50")
    _mark("resnet50", t0)

    # config #2 accuracy leg: cats-vs-dogs-shaped convergence
    t0 = time.time()
    if _remaining() > 180:
        try:
            extra["resnet_accuracy"] = bench_resnet_accuracy(accel)
        except Exception as e:
            extra["resnet_accuracy_error"] = f"{type(e).__name__}: {e}"
    else:
        _skip(extra, "resnet_accuracy")
    _mark("resnet_accuracy", t0)

    # Pallas flash attention on silicon vs the STOCK pallas kernel
    # (flash-vs-stock at L∈{1k,2k,8k}) — fwd pinning at every length;
    # the section closes the run and degrades per-length.
    t0 = time.time()
    # bwd pinning at L2048 rides along when the window allows (2 extra
    # kernel compiles ~40s); fwd at all three lengths is the must-have
    specs = [(2048, dict(include_bwd=_remaining() > 190,
                         include_blockwise=False))]
    if _remaining() > 100:
        specs.append((8192, dict(include_bwd=False,
                                 include_blockwise=False)))
    else:
        _skip(extra, "attention_l8192")
    if _remaining() > 140:
        specs.append((1024, dict(include_bwd=False,
                                 include_blockwise=False)))
    else:
        _skip(extra, "attention_l1024")
    try:
        bench_attention_suite(accel, specs, into=extra)
    except Exception as e:
        extra["attention_error"] = f"{type(e).__name__}: {e}"
    _mark("attention", t0)
    report["value"] = round(value, 1)
    report["vs_baseline"] = round(vs_baseline, 3) if vs_baseline else None
    watchdog.emit()
    failed = _error_keys(extra)
    if failed:
        # the report above keeps what did complete; the exit code says
        # the run is not a clean measurement
        print(f"[bench] sections raised: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
