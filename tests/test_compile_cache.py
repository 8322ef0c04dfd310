"""Persistent AOT compile cache (deploy/compile_cache.py).

The warm-start contract (docs/SERVING.md "Warm start & multi-model"):

- a COLD process pays one live XLA compile per (model, bucket) program
  and persists each serialized executable; a WARM process pre-installs
  them all via ``warm()`` and reaches full bucket coverage with
  ``compile_count == 0`` — proven in-process here and across a REAL
  process boundary by the slow ``serving_warm`` mp_harness test;
- the corruption matrix (ISSUE satellite, mirroring
  test_dist_checkpoint.py): a truncated entry, a CRC-tampered payload
  and a bad magic each quarantine to ``<file>.corrupt`` and fall back
  to a clean recompile; a jax-version-skewed header is *detected*
  (``version_skew``), left on disk, and overwritten by the recompile;
- every outcome lands in
  ``serving_compile_cache_events_total{event,model}`` (+ flat mirrors);
- eviction: oldest-mtime entries beyond ``max_entries`` are GC'd;
- ``plan_buckets`` (ISSUE satellite) is THE shared bucket-overflow
  policy — predict() and DeviceExecutor._dispatch plan through the
  same function, so their program-shape sets can never disagree.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from analytics_zoo_tpu.core.profiling import TIMERS
from analytics_zoo_tpu.deploy import CompileCache, InferenceModel, plan_buckets
from analytics_zoo_tpu.deploy.compile_cache import (CompileCacheCorrupt,
                                                    cache_env)
from analytics_zoo_tpu.nn import Dense, Sequential, reset_name_scope
from analytics_zoo_tpu.nn.layers.core import Activation
from analytics_zoo_tpu.train.optimizers import Adam

BUCKETS = (1, 8)
IN_DIM, OUT_DIM = 12, 4


def _trained_net():
    reset_name_scope()
    net = Sequential([Dense(16, input_shape=(IN_DIM,)), Activation("relu"),
                      Dense(OUT_DIM)])
    net.compile(optimizer=Adam(1e-2), loss="mse")
    rs = np.random.RandomState(0)
    x = rs.randn(64, IN_DIM).astype(np.float32)
    net.fit(x, rs.randn(64, OUT_DIM).astype(np.float32), batch_size=32,
            nb_epoch=1, verbose=False)
    return net, x


def _model(net, buckets=BUCKETS):
    """A FRESH InferenceModel over the same trained net — same weights,
    same fingerprint, empty program table: a simulated process restart."""
    return InferenceModel.from_keras_net(net, net.estimator.params,
                                         net.estimator.state,
                                         batch_buckets=buckets)


def _entry_files(root):
    return sorted(fn for fn in os.listdir(root) if fn.endswith(".xc"))


def _cover_buckets(m, x):
    """Predict once per bucket; returns {bucket: output}."""
    return {b: np.asarray(m.predict(x[:b])) for b in m.batch_buckets}


class TestWarmStart:
    def test_cold_compiles_once_per_bucket_then_warm_restart_compiles_zero(
            self, tmp_path):
        net, x = _trained_net()
        cache = CompileCache(str(tmp_path))

        cold = _model(net).attach_compile_cache(cache, name="resnet")
        cold_out = _cover_buckets(cold, x)
        assert cold.compile_count == len(BUCKETS)
        assert cold.warm_count == 0
        assert len(_entry_files(tmp_path)) == len(BUCKETS)
        assert cache.stats()["events"].get("miss", 0) == len(BUCKETS)

        # "restart": a fresh model + fresh cache handle over the same dir
        cache2 = CompileCache(str(tmp_path))
        warm = _model(net).attach_compile_cache(cache2, name="resnet")
        assert warm.warm() == len(BUCKETS)
        warm_out = _cover_buckets(warm, x)
        assert warm.compile_count == 0, (
            "warm restart paid a live compile for a cached shape")
        assert warm.warm_count == len(BUCKETS)
        assert cache2.stats()["events"].get("hit", 0) >= len(BUCKETS)
        for b in BUCKETS:
            np.testing.assert_allclose(cold_out[b], warm_out[b],
                                       rtol=1e-5, atol=1e-6)

    def test_repeat_predict_on_warm_shape_loads_once(self, tmp_path):
        net, x = _trained_net()
        cache = CompileCache(str(tmp_path))
        m = _model(net).attach_compile_cache(cache)
        for _ in range(3):
            m.predict(x[:1])
        # one miss+store, then the in-memory program table answers
        assert m.compile_count == 1
        assert cache.stats()["events"] == {"miss": 1}

    def test_fingerprint_isolates_models(self, tmp_path):
        """A second model with different weights must not warm from the
        first model's executables."""
        import jax

        net_a, x = _trained_net()
        cache = CompileCache(str(tmp_path))
        _cover_buckets(_model(net_a).attach_compile_cache(cache), x)

        perturbed = jax.tree_util.tree_map(lambda a: a + 1.0,
                                           net_a.estimator.params)
        mb = InferenceModel.from_keras_net(
            net_a, perturbed, net_a.estimator.state, batch_buckets=BUCKETS
        ).attach_compile_cache(CompileCache(str(tmp_path)))
        ma = _model(net_a)
        assert mb.fingerprint() != ma.fingerprint()
        assert mb.warm() == 0

    def test_attach_requires_native_net(self):
        m = InferenceModel.from_function(lambda x: x * 2.0)
        with pytest.raises(ValueError, match="native net"):
            m.attach_compile_cache(CompileCache("/tmp/unused"))


class TestCorruptionMatrix:
    """Mirror of test_dist_checkpoint.py's corruption matrix: each
    damage flavour quarantines (or detects) the entry, counts the event,
    and the caller recovers with a clean recompile."""

    def _one_entry(self, tmp_path):
        net, x = _trained_net()
        cache = CompileCache(str(tmp_path))
        m = _model(net, buckets=(8,)).attach_compile_cache(cache)
        m.predict(x[:8])
        files = _entry_files(tmp_path)
        assert len(files) == 1
        return net, x, os.path.join(str(tmp_path), files[0])

    def _assert_quarantined_then_recompiles(self, tmp_path, net, x, path):
        n0 = TIMERS.count("serving/compile_cache_corrupt")
        cache = CompileCache(str(tmp_path))
        m = _model(net, buckets=(8,)).attach_compile_cache(cache)
        assert m.warm() == 0
        assert os.path.exists(path + ".corrupt")
        assert not os.path.exists(path)
        assert cache.stats()["events"].get("corrupt", 0) >= 1
        assert TIMERS.count("serving/compile_cache_corrupt") > n0
        # clean recompile re-stores under the same digest
        m.predict(x[:8])
        assert m.compile_count == 1
        assert os.path.exists(path)

    def test_truncated_entry_quarantined(self, tmp_path):
        net, x, path = self._one_entry(tmp_path)
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[:len(data) // 2])
        self._assert_quarantined_then_recompiles(tmp_path, net, x, path)

    def test_payload_bitflip_fails_crc(self, tmp_path):
        net, x, path = self._one_entry(tmp_path)
        data = bytearray(open(path, "rb").read())
        data[-1] ^= 0xFF
        with open(path, "wb") as f:
            f.write(bytes(data))
        self._assert_quarantined_then_recompiles(tmp_path, net, x, path)

    def test_bad_magic_quarantined(self, tmp_path):
        net, x, path = self._one_entry(tmp_path)
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(b"NOPE" + data[4:])
        self._assert_quarantined_then_recompiles(tmp_path, net, x, path)

    def test_read_entry_raises_typed_error(self, tmp_path):
        _, _, path = self._one_entry(tmp_path)
        with open(path, "r+b") as f:
            f.truncate(6)
        with pytest.raises(CompileCacheCorrupt):
            CompileCache(str(tmp_path))._read_entry(path)

    def test_version_skew_detected_and_overwritten(self, tmp_path):
        """A header built under another jax build is a *detected* skew:
        the file stays on disk (no quarantine) and the caller's
        recompile overwrites the same digest in place."""
        import json
        import struct

        net, x, path = self._one_entry(tmp_path)
        data = open(path, "rb").read()
        (hlen,) = struct.unpack_from("<I", data, 4)
        header = json.loads(data[8:8 + hlen].decode("utf-8"))
        header["jax"] = "0.0.0-ancient"
        hdr = json.dumps(header, sort_keys=True).encode("utf-8")
        with open(path, "wb") as f:
            f.write(data[:4] + struct.pack("<I", len(hdr)) + hdr
                    + data[8 + hlen:])

        n0 = TIMERS.count("serving/compile_cache_version_skew")
        cache = CompileCache(str(tmp_path))
        m = _model(net, buckets=(8,)).attach_compile_cache(cache)
        assert m.warm() == 0
        assert os.path.exists(path), "skewed entry must stay, not vanish"
        assert not os.path.exists(path + ".corrupt")
        assert cache.stats()["events"].get("version_skew", 0) >= 1
        assert TIMERS.count("serving/compile_cache_version_skew") > n0

        m.predict(x[:8])            # recompile overwrites in place
        assert m.compile_count == 1
        hdr2 = CompileCache(str(tmp_path))._read_entry(path)[0]
        assert hdr2["jax"] == cache_env()["jax"]

    def test_torn_store_leaves_no_entry(self, tmp_path, monkeypatch):
        """A crash mid-store must never leave a half-written file under
        the real entry name (atomic tmp + os.replace)."""
        net, x = _trained_net()
        cache = CompileCache(str(tmp_path))

        def boom(src, dst):
            raise OSError("disk died mid-replace")

        monkeypatch.setattr(os, "replace", boom)
        m = _model(net, buckets=(8,)).attach_compile_cache(cache)
        with pytest.raises(OSError):
            m.predict(x[:8])
        monkeypatch.undo()
        assert _entry_files(tmp_path) == []
        assert all(not fn.endswith(".tmp") for fn in os.listdir(tmp_path))


class TestEviction:
    def test_gc_evicts_oldest_beyond_cap(self, tmp_path):
        net, x = _trained_net()
        cache = CompileCache(str(tmp_path), max_entries=2)
        m = _model(net, buckets=(1, 4, 8)).attach_compile_cache(cache)
        times = iter([100.0, 200.0, 300.0])
        for b in (1, 4, 8):
            m.predict(x[:b])
            path = os.path.join(str(tmp_path), _entry_files(tmp_path)[-1])
            t = next(times)
            for fn in _entry_files(tmp_path):
                p = os.path.join(str(tmp_path), fn)
                if os.path.getmtime(p) > t:
                    os.utime(p, (t, t))
        assert len(_entry_files(tmp_path)) == 2, (
            "store() must gc to max_entries")
        assert len(cache.entries()) == 2


class TestPlanBuckets:
    """Satellite: the single shared bucket-overflow policy."""

    def test_exact_and_padded_fits(self):
        assert plan_buckets(5, (8, 64)) == [(5, 8)]
        assert plan_buckets(8, (8, 64)) == [(8, 8)]
        assert plan_buckets(64, (8, 64)) == [(64, 64)]

    def test_overflow_splits_into_full_bucket_programs(self):
        assert plan_buckets(100, (8, 64)) == [(64, 64), (36, 64)]
        assert plan_buckets(70, (8, 64)) == [(64, 64), (6, 8)]
        assert plan_buckets(129, (8, 64)) == [(64, 64), (64, 64), (1, 8)]

    def test_rows_conserved_and_buckets_legal(self):
        buckets = (1, 8, 64)
        for n in (1, 7, 63, 65, 200):
            plan = plan_buckets(n, buckets)
            assert sum(m for m, _ in plan) == n
            assert all(b in buckets and m <= b for m, b in plan)

    def test_predict_and_executor_share_the_policy(self):
        from analytics_zoo_tpu.deploy import inference, serving

        assert serving.plan_buckets is inference.plan_buckets


@pytest.mark.slow
def test_warm_restart_across_real_processes(tmp_path):
    """The two-process proof (ISSUE satellite): process A cold-compiles
    and persists; process B — a REAL separate OS process against the
    same cache dir — must reach full bucket coverage with zero live
    compiles and only ``hit`` events."""
    from tests.mp_harness import run_workers

    cache_dir = tmp_path / "xcache"
    cold = run_workers(1, tmp_path, "xc_cold", scenario="serving_warm",
                       ckpt_dir=cache_dir, global_devices=1)[0]
    nb = len(cold["buckets"])
    assert cold["compile_count"] == nb
    assert cold["warm_count"] == 0
    assert cold["cache"]["events"].get("miss", 0) == nb

    warm = run_workers(1, tmp_path, "xc_warm", scenario="serving_warm",
                       ckpt_dir=cache_dir, global_devices=1)[0]
    assert warm["fingerprint"] == cold["fingerprint"], (
        "deterministic build must fingerprint identically across processes")
    assert warm["compile_count"] == 0, (
        "second process paid live compiles despite a full cache")
    assert warm["warm_count"] == nb
    assert warm["cache"]["events"].get("hit", 0) >= nb
    assert warm["cache"]["events"].get("corrupt", 0) == 0
    for b, v in cold["pred_sums"].items():
        assert abs(warm["pred_sums"][b] - v) < 1e-3


_REPLICA_RESTART = """
import json, sys
import numpy as np
import jax
from tests.test_compile_cache import _model, _trained_net
from analytics_zoo_tpu.deploy import CompileCache

net, x = _trained_net()
m = _model(net).attach_compile_cache(CompileCache(sys.argv[1]))
warmed = m.warm()
sums = [float(np.asarray(rep.harvest(rep.dispatch([x[:b]]))[0]).sum())
        for rep in m.replica_forwards(n=len(jax.devices()))
        for b in m.batch_buckets]
print("RESULT", json.dumps({"devices": len(jax.devices()),
                            "warmed": int(warmed), "sums": sums,
                            "compile_count": int(m.compile_count)}))
"""


def test_per_device_replicas_warm_start_on_a_multi_device_host(tmp_path):
    """A restarted process on a four-device host runs every persisted
    one-device replica program ON ITS OWN DEVICE with zero live compiles.
    (jax's deserialize_and_load assumes every backend device unless told
    otherwise: such a program then demanded four argument shards and the
    restart died at its first dispatch — only ever visible across a real
    process boundary, on more than one device.)"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")

    def run():
        proc = subprocess.run(
            [sys.executable, "-c", _REPLICA_RESTART, str(tmp_path / "xc")],
            env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("RESULT ")][-1]
        return json.loads(line[len("RESULT "):])

    cold, warm = run(), run()
    n = 4 * len(BUCKETS)
    assert cold["devices"] == 4 and cold["compile_count"] == n
    assert warm["warmed"] == n and warm["compile_count"] == 0
    np.testing.assert_allclose(warm["sums"], cold["sums"], rtol=1e-5)
