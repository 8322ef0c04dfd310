"""Persistent AOT compile cache: serialized XLA executables on disk.

A serving process pays one XLA compile per (model, bucket shape, dtype,
device) program signature.  On a restart every one of those compiles is
paid again before the worker reaches full bucket coverage — the
dominant term in restart-to-SLO time (docs/SERVING.md "Warm start &
multi-model").  The reference stack dodged this by
loading pre-built OpenVINO engine blobs (PAPER.md §L0); the TPU-native
equivalent is ``jax.jit(fwd).lower(...).compile()`` +
``jax.experimental.serialize_executable``: the compiled executable
serializes to bytes, and a restarted process deserializes it back in
milliseconds instead of re-tracing and re-compiling.

Entry layout (one file per program, content-addressed)::

    <digest>.xc := MAGIC("AZXC") | u32 header_len | header_json
                   | u32 crc32(payload) | u64 payload_len | payload

``digest = sha256(fingerprint, sig)`` where ``sig`` carries the input
shapes/dtypes, target device, fused top-N and the mesh descriptor
(platform x device count).  The jax/jaxlib versions live in the HEADER,
not the digest: a version mismatch is *detected* at load
(``version_skew``) and the caller's recompile overwrites the same file
in place — an invisible miss would leave stale executables pinned on
disk forever.

Failure semantics mirror ``train/checkpoint.py`` snapshots: payload CRC
verified on every load; a torn/truncated/unparseable entry is
quarantined to ``<file>.corrupt`` and the caller falls back to a clean
recompile.  Writes are atomic (tmp + ``os.replace``) so a crash
mid-store never leaves a half-written entry under the real name.

Every entry records the devices its program was lowered for and is
loaded onto exactly those (jax would otherwise assume every backend
device).  Known limit, seen on a four-chip v5e host: the TPU client of
jax 0.9.0 / libtpu 0.0.34 binds every restored one-device program to
device 0 whatever it is asked (and reports the asked device), so a
per-device replica on chips 1..n cannot warm-start there — its first
dispatch raises.  One replica on chip 0, the serving default, is
unaffected (PERF.md, open questions).

Every outcome is counted in
``serving_compile_cache_events_total{event=hit|miss|corrupt|version_skew}``
with the owning model as a ``model`` label (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import struct
import tempfile
import threading
import time
import zlib
from typing import Any, Dict, Iterator, List, Optional, Tuple

__all__ = ["CompileCache", "CompileCacheCorrupt", "cache_env"]

logger = logging.getLogger("analytics_zoo_tpu.deploy")

_MAGIC = b"AZXC"
_HDR = struct.Struct("<I")      # header_len
_PAY = struct.Struct("<IQ")     # crc32(payload), payload_len


class CompileCacheCorrupt(Exception):
    """A cache entry failed structural validation (magic/CRC/length)."""


def cache_env() -> Dict[str, str]:
    """The toolchain identity an executable is only valid under.

    ``jax``/``jaxlib`` versions gate deserialization (an executable
    serialized by one XLA build is not guaranteed loadable by another);
    ``mesh`` (platform x visible device count) joins the *digest* so a
    4-chip cache never collides with an 8-chip one.
    """
    import jax
    import jaxlib

    devs = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": getattr(jaxlib, "__version__", "unknown"),
        "mesh": f"{devs[0].platform}x{len(devs)}",
    }


def _assignment(sharding) -> list:
    """The devices of one input sharding in device-assignment order: a
    mesh's flat device order, else (one device, or no mesh) by id."""
    mesh = getattr(sharding, "mesh", None)
    if mesh is not None:
        return list(mesh.devices.flat)
    return sorted(sharding.device_set, key=lambda d: d.id)


class CompileCache:
    """Content-addressed on-disk store of serialized XLA executables.

    One instance may be shared by every model in a multi-model worker;
    the in-memory ledger (``_index``) and event counts are guarded by
    ``_lock`` — loads/stores arrive concurrently from replica dispatch
    threads and the warm() path.
    """

    SUFFIX = ".xc"

    def __init__(self, root: str, max_entries: int = 512):
        self.root = str(root)
        self.max_entries = max(1, int(max_entries))
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        # digest -> header of entries this process has seen intact
        self._index: Dict[str, Dict[str, Any]] = {}
        self._events: Dict[str, int] = {}

    # -- keying ------------------------------------------------------------

    @staticmethod
    def digest(fingerprint: str, sig: Dict[str, Any]) -> str:
        """Content address for one program: model fingerprint + program
        signature + mesh descriptor (NOT the jax version — see module
        docstring)."""
        blob = json.dumps({"fp": fingerprint, "sig": sig,
                           "mesh": cache_env()["mesh"]}, sort_keys=True)
        return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:40]

    def path_for(self, fingerprint: str, sig: Dict[str, Any]) -> str:
        return os.path.join(self.root,
                            self.digest(fingerprint, sig) + self.SUFFIX)

    # -- events ------------------------------------------------------------

    def _event(self, event: str, model: str) -> None:
        from analytics_zoo_tpu.observe import metrics as obs

        with self._lock:
            self._events[event] = self._events.get(event, 0) + 1
        obs.count("serving_compile_cache_events_total", event=event,
                  model=model, flat=f"serving/compile_cache_{event}")

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            events = dict(self._events)
            indexed = len(self._index)
        return {"root": self.root, "events": events, "indexed": indexed,
                "entries": len(self._entry_files())}

    # -- store -------------------------------------------------------------

    def store(self, fingerprint: str, sig: Dict[str, Any], compiled,
              model: str = "default") -> str:
        """Serialize one compiled executable; atomic overwrite-in-place
        (version-skewed or stale entries at the same digest are simply
        replaced).  Returns the entry path."""
        import jax
        from jax.experimental import serialize_executable

        blob, in_tree, out_tree = serialize_executable.serialize(compiled)
        payload = pickle.dumps((blob, in_tree, out_tree),
                               protocol=pickle.HIGHEST_PROTOCOL)
        # the devices the program was lowered for, in assignment order:
        # jax's deserialize_and_load otherwise assumes EVERY backend
        # device, and a one-device replica program loaded on a four-chip
        # host then demands four argument shards.  Read from the input
        # shardings, not the runtime executable: on TPU a one-device
        # program is loaded portable and reports the default device.
        device_ids = [d.id for d in _assignment(
            jax.tree_util.tree_leaves(compiled.input_shardings)[0])]
        header = dict(fingerprint=fingerprint, sig=sig, model=model,
                      created=time.time(), device_ids=device_ids,
                      **cache_env())
        hdr = json.dumps(header, sort_keys=True).encode("utf-8")
        crc = zlib.crc32(payload) & 0xFFFFFFFF
        path = self.path_for(fingerprint, sig)
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(_MAGIC)
                f.write(_HDR.pack(len(hdr)))
                f.write(hdr)
                f.write(_PAY.pack(crc, len(payload)))
                f.write(payload)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        with self._lock:
            self._index[os.path.basename(path)[:-len(self.SUFFIX)]] = header
        self.gc()
        return path

    # -- load --------------------------------------------------------------

    def _read_entry(self, path: str) -> Tuple[Dict[str, Any], bytes]:
        """Parse + CRC-check one entry; raises CompileCacheCorrupt on any
        structural damage (torn write, truncation, bit rot)."""
        with open(path, "rb") as f:
            data = f.read()
        if len(data) < len(_MAGIC) + _HDR.size or \
                data[:len(_MAGIC)] != _MAGIC:
            raise CompileCacheCorrupt(f"{path}: bad magic")
        off = len(_MAGIC)
        (hlen,) = _HDR.unpack_from(data, off)
        off += _HDR.size
        if off + hlen + _PAY.size > len(data):
            raise CompileCacheCorrupt(f"{path}: truncated header")
        try:
            header = json.loads(data[off:off + hlen].decode("utf-8"))
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise CompileCacheCorrupt(f"{path}: unparseable header: {e}")
        off += hlen
        crc, plen = _PAY.unpack_from(data, off)
        off += _PAY.size
        payload = data[off:off + plen]
        if len(payload) != plen:
            raise CompileCacheCorrupt(
                f"{path}: truncated payload ({len(payload)}/{plen} bytes)")
        if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
            raise CompileCacheCorrupt(f"{path}: payload CRC mismatch")
        return header, payload

    def _quarantine(self, path: str, model: str, why: str) -> None:
        self._event("corrupt", model)
        logger.warning("compile cache: quarantining %s (%s)", path, why)
        try:
            os.replace(path, path + ".corrupt")
        except OSError:
            pass
        with self._lock:
            self._index.pop(
                os.path.basename(path)[:-len(self.SUFFIX)], None)

    @staticmethod
    def _version_ok(header: Dict[str, Any]) -> bool:
        env = cache_env()
        return (header.get("jax") == env["jax"]
                and header.get("jaxlib") == env["jaxlib"])

    @staticmethod
    def _deserialize(header: Dict[str, Any], payload: bytes):
        import jax
        from jax.experimental import serialize_executable

        by_id = {d.id: d for d in jax.devices()}
        blob, in_tree, out_tree = pickle.loads(payload)
        return serialize_executable.deserialize_and_load(
            blob, in_tree, out_tree,
            execution_devices=[by_id[i] for i in header["device_ids"]])

    def load(self, fingerprint: str, sig: Dict[str, Any],
             model: str = "default"):
        """One executable, or None (caller compiles + ``store``\\ s).

        Counts exactly one of ``hit`` / ``miss`` / ``corrupt`` /
        ``version_skew``.  A skewed entry stays on disk: the caller's
        recompile stores to the same digest and overwrites it."""
        path = self.path_for(fingerprint, sig)
        if not os.path.exists(path):
            self._event("miss", model)
            return None
        try:
            header, payload = self._read_entry(path)
        except CompileCacheCorrupt as e:
            self._quarantine(path, model, str(e))
            return None
        if not self._version_ok(header):
            self._event("version_skew", model)
            logger.warning(
                "compile cache: %s built under jax %s/jaxlib %s; current "
                "is %s — recompiling and overwriting", path,
                header.get("jax"), header.get("jaxlib"),
                cache_env()["jax"])
            return None
        try:
            compiled = self._deserialize(header, payload)
        except Exception as e:
            # structurally intact but undeserializable (e.g. an XLA
            # build mismatch the version header didn't capture)
            self._quarantine(path, model, f"deserialize failed: {e}")
            return None
        with self._lock:
            self._index[os.path.basename(path)[:-len(self.SUFFIX)]] = header
        self._event("hit", model)
        return compiled

    def load_all(self, fingerprint: str, model: str = "default"
                 ) -> Iterator[Tuple[Dict[str, Any], Any]]:
        """Every intact, version-compatible entry for one model
        fingerprint — the warm() path: a restarted worker pre-installs
        full bucket coverage without needing to see a single request.
        Yields ``(sig, compiled)``; each successful load counts ``hit``."""
        for path in self._entry_files():
            try:
                header, payload = self._read_entry(path)
            except CompileCacheCorrupt as e:
                self._quarantine(path, model, str(e))
                continue
            if header.get("fingerprint") != fingerprint:
                continue
            if not self._version_ok(header):
                self._event("version_skew", model)
                continue
            try:
                compiled = self._deserialize(header, payload)
            except Exception as e:
                self._quarantine(path, model, f"deserialize failed: {e}")
                continue
            with self._lock:
                self._index[os.path.basename(path)[:-len(self.SUFFIX)]] = \
                    header
            self._event("hit", model)
            yield header["sig"], compiled

    # -- housekeeping ------------------------------------------------------

    def _entry_files(self) -> List[str]:
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        return sorted(os.path.join(self.root, fn) for fn in names
                      if fn.endswith(self.SUFFIX))

    def entries(self) -> List[Dict[str, Any]]:
        """Headers of every intact entry (corrupt ones skipped, not
        quarantined — this is a read-only listing)."""
        out = []
        for path in self._entry_files():
            try:
                header, _ = self._read_entry(path)
            except CompileCacheCorrupt:
                continue
            out.append(header)
        return out

    def gc(self, max_entries: Optional[int] = None) -> int:
        """Evict oldest-mtime entries beyond the cap (docs/SERVING.md
        "Warm start & multi-model" — eviction is LRU-by-mtime because a
        warm() sweep re-reads, and thereby touches, every live entry).
        Returns the number evicted."""
        cap = max_entries if max_entries is not None else self.max_entries
        files = self._entry_files()
        if len(files) <= cap:
            return 0
        def _mtime(p):
            try:
                return os.path.getmtime(p)
            except OSError:
                return 0.0
        files.sort(key=_mtime)
        evicted = 0
        for path in files[:len(files) - cap]:
            try:
                os.unlink(path)
                evicted += 1
            except OSError:
                continue
            with self._lock:
                self._index.pop(
                    os.path.basename(path)[:-len(self.SUFFIX)], None)
        return evicted
