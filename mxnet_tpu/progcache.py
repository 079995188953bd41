"""mxnet_tpu.progcache — persistent on-disk cache of compiled XLA programs.

Both the XLA operator-fusion study and TVM (PAPERS.md) argue that the
small set of shape-specialized compiled programs IS the framework's
performance asset — yet every process start used to rebuild that asset
from scratch: a restarted ``InferenceServer`` suffered a cold-start
compile storm across its whole bucket ladder, and every train job
re-lowered and re-compiled its fused step. This module persists the
asset:

- **Content-addressed entries.** Each cached program is one file,
  ``<key>.prog``, where ``key`` is a sha1 over (model fingerprint,
  input names/shapes/dtypes, backend + device kind, jax/jaxlib/package
  versions, donation config). The *model fingerprint* for a Predictor
  hashes the symbol JSON plus every parameter's name/shape/dtype/CRC —
  parameter values are closure-baked constants inside the serialized
  executable, so a cache hit with different weights would silently serve
  a stale model; hashing the bytes makes that a miss instead. The train
  step's ``update_fn`` is arbitrary Python, so its key hashes the
  lowered StableHLO text (the only faithful capture of the program).
- **Self-verifying entry format.** ``MXTPUPROG\\x01`` magic, a JSON meta
  block (versions, backend, key), a CRC32 of the payload, then the
  payload: the pickled ``(bytes, in_tree, out_tree)`` triple from
  ``jax.experimental.serialize_executable``. Loads verify magic, meta,
  version skew, and CRC before deserializing; ANY failure (truncation,
  corruption, skew, deserialize error) is a silent fallback to a fresh
  compile, counted in ``progcache_fallbacks``. The cache can only make
  startup faster, never answers wrong.
- **Atomic commits.** Every file write goes through
  :func:`_atomic_write_bytes` — tmp + fsync + ``os.replace``, the same
  commit idiom as ``resilience.checkpoint`` — so a crash mid-write can
  never leave a half-entry at the committed name. The analysis stage-7
  checker ``progcache_io`` enforces this for the module.
- **CRC-checked manifest + LRU byte budget.** ``manifest.json`` holds
  per-entry byte sizes and LRU clocks plus persisted bucket ladders;
  it is advisory — corruption or cross-process races rebuild it from a
  directory scan (entries are content-addressed, the manifest is never
  needed for correctness). Total bytes are bounded by
  ``MXNET_PROGCACHE_BYTES`` (default 2 GiB), evicting oldest-clock
  entries first.

Enablement: the cache is OFF unless ``MXNET_PROGCACHE_DIR`` is set (or
``MXNET_PROGCACHE=1``, which uses ``~/.cache/mxnet_tpu/progcache``);
``MXNET_PROGCACHE=0`` is the kill switch that wins over everything.
Sharing one cache dir across replicas/processes is supported: commits
are atomic renames, loads go straight to the content-addressed file,
and manifest races are last-writer-wins on advisory data only.

Telemetry: ``progcache_hits`` / ``progcache_misses`` /
``progcache_fallbacks`` counters and a ``progcache_bytes`` gauge in the
unified registry, plus ``progcache.load`` / ``progcache.store`` tracer
spans (domain ``progcache``).
"""
from __future__ import annotations

import binascii
import hashlib
import json
import logging
import os
import pickle
import struct
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from . import telemetry as _telemetry

log = logging.getLogger("mxnet_tpu")

MAGIC = b"MXTPUPROG\x01"
MANIFEST = "manifest.json"
MANIFEST_VERSION = 1
DEFAULT_BUDGET = 2 << 30  # 2 GiB
_U32 = struct.Struct("<I")

# Serializes manifest read-modify-write and the session stat dict.
# Declared leaf (rank 100) in analysis.lockorder.LOCK_HIERARCHY: nothing
# ranked is ever acquired under it, and telemetry increments happen
# outside holds of it.
_lock = threading.Lock()

# Session counters (mirrored into the telemetry registry; kept here too so
# stats() works even with MXNET_TELEMETRY=0).
_stats = {"hits": 0, "misses": 0, "fallbacks": 0, "stores": 0,
          "evictions": 0}

# Bytes in use per cache dir, refreshed on every manifest load/commit —
# the progcache_bytes gauge reads this instead of hitting the disk.
_bytes_by_dir: Dict[str, int] = {}

# Same, split by entry kind (predictor / train_step / "" for legacy
# entries) — a per-kind gauge is registered lazily when a kind
# first appears so the exposition only grows for kinds actually in use.
_bytes_by_dir_kind: Dict[str, Dict[str, int]] = {}
_kind_gauges: Dict[str, object] = {}

_hits = _telemetry.registry.counter(
    "progcache_hits", "persistent program cache: successful disk loads")
_misses = _telemetry.registry.counter(
    "progcache_misses", "persistent program cache: key not present")
_fallbacks = _telemetry.registry.counter(
    "progcache_fallbacks",
    "persistent program cache: entry present but unusable "
    "(corruption/version skew/deserialize failure) — fell back to compile")
_telemetry.registry.gauge(
    "progcache_bytes", lambda: float(sum(_bytes_by_dir.values())),
    "persistent program cache: bytes on disk (all dirs used this process)")


# --- enablement -----------------------------------------------------------

def cache_dir() -> Optional[str]:
    """The active cache directory, or None when the cache is disabled.

    Read at point of use (like the telemetry kill switch) so tests and
    operators can flip it per-process without code changes."""
    flag = os.environ.get("MXNET_PROGCACHE", "").strip().lower()
    if flag in ("0", "off", "false", "none"):
        return None  # kill switch wins over MXNET_PROGCACHE_DIR
    d = os.environ.get("MXNET_PROGCACHE_DIR", "").strip()
    if d:
        return d
    if flag in ("1", "on", "true"):
        return os.path.join(os.path.expanduser("~"), ".cache", "mxnet_tpu",
                            "progcache")
    return None


def enabled() -> bool:
    return cache_dir() is not None


def byte_budget() -> int:
    try:
        return int(os.environ.get("MXNET_PROGCACHE_BYTES", DEFAULT_BUDGET))
    except ValueError:
        return DEFAULT_BUDGET


# --- atomic commit (the resilience.checkpoint idiom) ----------------------

def _atomic_write_bytes(path: str, data: bytes):
    """tmp + fsync + os.replace: the committed name either holds the old
    content or the complete new content, never a torn write. The ONLY
    function in this module allowed to open files for writing (enforced
    by the ``progcache_io`` analysis checker)."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


# --- fingerprints / keys --------------------------------------------------

def _runtime_meta() -> Dict[str, str]:
    """The environment facts a cached executable is only valid under."""
    import jax
    import jaxlib

    try:
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = "unknown"
    from .base import __version__ as pkg_version
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "mxnet_tpu": pkg_version,
        "backend": jax.default_backend(),
        "device_kind": kind,
    }


def _param_digest(h, name: str, arr) -> None:
    """Fold one parameter into ``h``: name/shape/dtype AND a CRC of the
    bytes. Values matter — jit closure constants are baked into the
    serialized executable, so two weight sets must never share a key."""
    import numpy as np

    data = np.asarray(getattr(arr, "_data", arr))
    h.update(name.encode())
    h.update(str(data.shape).encode())
    h.update(str(data.dtype).encode())
    h.update(_U32.pack(binascii.crc32(data.tobytes()) & 0xFFFFFFFF))


def model_fingerprint(symbol, arg_params: Dict, aux_params: Dict) -> str:
    """sha1 over the symbol graph + every parameter's name/shape/dtype/CRC.
    This is the 'same model, same weights' identity that predictor keys
    and persisted ladders hang off."""
    h = hashlib.sha1()
    h.update(symbol.tojson().encode())
    for name in sorted(arg_params):
        _param_digest(h, "arg:" + name, arg_params[name])
    for name in sorted(aux_params):
        _param_digest(h, "aux:" + name, aux_params[name])
    return h.hexdigest()


def predictor_key(model_fp: str, input_names: Sequence[str],
                  input_shapes: Dict[str, tuple], dtype: str,
                  device: Optional[object] = None) -> str:
    """Cache key for a Predictor program: model identity + the bound
    input signature + the runtime facts. Computable WITHOUT lowering —
    warm hits skip jax.jit/lower entirely, which is what makes a warm
    restart ≥3× faster than a cold one."""
    h = hashlib.sha1()
    h.update(b"predict\x00")
    h.update(model_fp.encode())
    for n in input_names:
        h.update(n.encode())
        h.update(str(tuple(input_shapes[n])).encode())
    h.update(str(dtype).encode())
    if device is not None:
        h.update(repr(device).encode())
    h.update(json.dumps(_runtime_meta(), sort_keys=True).encode())
    return h.hexdigest()


def lowered_key(lowered_text: str, donate: Sequence[int] = (),
                extra: str = "") -> str:
    """Cache key for an arbitrary lowered computation (the fused train
    step): ``update_fn`` is arbitrary Python, so only the lowered
    StableHLO text captures it faithfully. Donation config is part of the
    key — a donating and a non-donating compile of the same HLO are
    different programs."""
    h = hashlib.sha1()
    h.update(b"lowered\x00")
    h.update(lowered_text.encode())
    h.update(str(tuple(donate)).encode())
    if extra:
        h.update(extra.encode())
    h.update(json.dumps(_runtime_meta(), sort_keys=True).encode())
    return h.hexdigest()


# --- manifest -------------------------------------------------------------

def _entries_crc(entries: Dict, ladders: Dict, clock: int) -> int:
    blob = json.dumps([entries, ladders, clock], sort_keys=True).encode()
    return binascii.crc32(blob) & 0xFFFFFFFF


def _entry_kind(path: str) -> str:
    """The ``kind`` from an entry file's meta header (manifest rebuild
    only reads the small header, never the payload)."""
    try:
        with open(path, "rb") as f:
            head = f.read(len(MAGIC) + 4)
            if not head.startswith(MAGIC):
                return ""
            (mlen,) = _U32.unpack_from(head, len(MAGIC))
            meta = json.loads(f.read(mlen).decode())
        return str(meta.get("kind", ""))
    except Exception:
        return ""


def _load_manifest(d: str) -> Dict:
    """Read + CRC-verify the manifest; rebuild from a directory scan when
    missing or corrupt (the manifest is advisory — entries are
    content-addressed, so a rebuild loses only LRU clocks/ladders)."""
    path = os.path.join(d, MANIFEST)
    try:
        with open(path, "rb") as f:
            m = json.loads(f.read().decode())
        if (m.get("version") == MANIFEST_VERSION and
                m.get("crc") == _entries_crc(m.get("entries", {}),
                                             m.get("ladders", {}),
                                             m.get("clock", 0))):
            return m
        log.warning("progcache: manifest CRC mismatch at %s — rebuilding",
                    path)
    except FileNotFoundError:
        pass
    except Exception as e:  # corrupt JSON, unreadable, ...
        log.warning("progcache: unreadable manifest at %s (%s) — rebuilding",
                    path, e)
    entries = {}
    try:
        names = os.listdir(d)
    except OSError:
        names = []
    for fn in names:
        if fn.endswith(".prog"):
            try:
                sz = os.path.getsize(os.path.join(d, fn))
            except OSError:
                continue
            e = {"bytes": sz, "clock": 0}
            kind = _entry_kind(os.path.join(d, fn))
            if kind:
                e["kind"] = kind
            entries[fn[:-len(".prog")]] = e
    return {"version": MANIFEST_VERSION, "clock": 0, "entries": entries,
            "ladders": {}, "crc": _entries_crc(entries, {}, 0)}


def _refresh_kind_bytes(d: str, m: Dict):
    by_kind: Dict[str, int] = {}
    for e in m["entries"].values():
        k = e.get("kind", "")
        by_kind[k] = by_kind.get(k, 0) + e.get("bytes", 0)
    _bytes_by_dir_kind[d] = by_kind
    for k in by_kind:
        if k and k not in _kind_gauges:
            _kind_gauges[k] = _telemetry.registry.gauge(
                "progcache_bytes_kind_" + k,
                lambda _k=k: float(sum(
                    bk.get(_k, 0) for bk in _bytes_by_dir_kind.values())),
                "persistent program cache: bytes on disk for %r entries"
                % k)


def _commit_manifest(d: str, m: Dict):
    m["crc"] = _entries_crc(m["entries"], m.get("ladders", {}), m["clock"])
    _atomic_write_bytes(os.path.join(d, MANIFEST),
                        json.dumps(m, sort_keys=True).encode())
    _bytes_by_dir[d] = sum(e.get("bytes", 0) for e in m["entries"].values())
    _refresh_kind_bytes(d, m)


def _evict_over_budget(d: str, m: Dict, protect: str) -> List[str]:
    """Drop oldest-clock entries until total bytes fit the budget; the
    just-stored key is protected so a store is never a self-eviction."""
    budget = byte_budget()
    total = sum(e.get("bytes", 0) for e in m["entries"].values())
    victims: List[str] = []
    by_age = sorted((k for k in m["entries"] if k != protect),
                    key=lambda k: m["entries"][k].get("clock", 0))
    for k in by_age:
        if total <= budget:
            break
        total -= m["entries"][k].get("bytes", 0)
        del m["entries"][k]
        victims.append(k)
    for k in victims:
        try:
            os.remove(os.path.join(d, k + ".prog"))
        except OSError:
            pass
    return victims


# --- load / store ---------------------------------------------------------

def _entry_path(d: str, key: str) -> str:
    return os.path.join(d, key + ".prog")


def _pack_entry(meta: Dict, payload: bytes) -> bytes:
    mb = json.dumps(meta, sort_keys=True).encode()
    return b"".join([MAGIC, _U32.pack(len(mb)), mb,
                     _U32.pack(binascii.crc32(payload) & 0xFFFFFFFF),
                     payload])


def _unpack_entry(blob: bytes) -> Tuple[Dict, bytes]:
    """Parse + verify one entry file; raises ValueError on any damage."""
    if len(blob) < len(MAGIC) + 8 or not blob.startswith(MAGIC):
        raise ValueError("bad magic / truncated header")
    off = len(MAGIC)
    (mlen,) = _U32.unpack_from(blob, off)
    off += 4
    if len(blob) < off + mlen + 4:
        raise ValueError("truncated meta block")
    meta = json.loads(blob[off:off + mlen].decode())
    off += mlen
    (crc,) = _U32.unpack_from(blob, off)
    off += 4
    payload = blob[off:]
    if binascii.crc32(payload) & 0xFFFFFFFF != crc:
        raise ValueError("payload CRC mismatch")
    return meta, payload


def _check_meta(meta: Dict) -> Optional[str]:
    """None when the entry is valid in this process; else the skew."""
    want = _runtime_meta()
    for k, v in want.items():
        if meta.get(k) != v:
            return "%s %r != %r" % (k, meta.get(k), v)
    return None


def _count(which: str):
    with _lock:
        _stats[which] = _stats.get(which, 0) + 1
    if which == "hits":
        _hits.inc()
    elif which == "misses":
        _misses.inc()
    elif which == "fallbacks":
        _fallbacks.inc()


def _drop_bad_entry(d: str, key: str):
    """Best-effort removal of an entry that failed verification, so the
    fallback is paid once, not on every restart."""
    try:
        os.remove(_entry_path(d, key))
    except OSError:
        pass
    with _lock:
        m = _load_manifest(d)
        if key in m["entries"]:
            del m["entries"][key]
            try:
                _commit_manifest(d, m)
            except OSError:
                pass


def load(key: str, kind: str = ""):
    """The deserialized, loaded executable for ``key``, or None.

    None means 'compile fresh' — either a clean miss (counted in
    ``progcache_misses``) or a damaged/skewed entry (counted in
    ``progcache_fallbacks`` and deleted). Never raises. ``kind`` tags the
    hit for the compile witness (``analysis.compile_witness``) so disk
    loads are accounted per surface; empty skips the witness."""
    d = cache_dir()
    if d is None:
        return None
    path = _entry_path(d, key)
    with _telemetry.span("progcache.load", domain="progcache",
                         key=key[:12], hit=False) as sp:
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            _count("misses")
            return None
        except OSError as e:
            log.warning("progcache: unreadable entry %s (%s)", path, e)
            _count("fallbacks")
            return None
        try:
            meta, payload = _unpack_entry(blob)
            skew = _check_meta(meta)
            if skew is not None:
                raise ValueError("version skew: %s" % skew)
            import jax
            from jax.experimental import serialize_executable as _sx

            # load onto the devices the program was compiled for: the
            # default is every device of the backend, which turns a
            # one-device program into one that wants a shard per device
            by_id = {dev.id: dev for dev in jax.devices()}
            devices = [by_id[i] for i in meta["device_ids"]]
            serialized, in_tree, out_tree = pickle.loads(payload)
            exe = _sx.deserialize_and_load(serialized, in_tree, out_tree,
                                           execution_devices=devices)
            sp.annotate(hit=True)
        except Exception as e:
            log.warning("progcache: entry %s unusable (%s) — falling back "
                        "to fresh compile", path, e)
            _drop_bad_entry(d, key)
            _count("fallbacks")
            return None
    touch(key)
    _count("hits")
    if kind:
        from .analysis import compile_witness as _witness

        _witness.record_disk_load(kind, key=key)
    return exe


def store(key: str, compiled, note: str = "", kind: str = "") -> bool:
    """Serialize ``compiled`` and commit it under ``key`` atomically,
    then update the manifest and evict past the byte budget. ``kind``
    classifies the entry (``predictor`` / ``train_step`` / ``decode`` /
    ``quant``) for the per-kind byte accounting. Best-effort: returns
    False (never raises) when serialization or I/O fails — the caller
    already has its compiled program either way."""
    d = cache_dir()
    if d is None:
        return False
    with _telemetry.span("progcache.store", domain="progcache",
                         key=key[:12]):
        try:
            from jax.experimental import serialize_executable as _sx

            serialized, in_tree, out_tree = _sx.serialize(compiled)
            payload = pickle.dumps((serialized, in_tree, out_tree),
                                   protocol=pickle.HIGHEST_PROTOCOL)
            meta = dict(_runtime_meta())
            meta["key"] = key
            meta["device_ids"] = [
                dev.id
                for dev in compiled.runtime_executable().local_devices()]
            if note:
                meta["note"] = note
            if kind:
                meta["kind"] = kind
            blob = _pack_entry(meta, payload)
            os.makedirs(d, exist_ok=True)
            _atomic_write_bytes(_entry_path(d, key), blob)
        except Exception as e:
            log.warning("progcache: store of %s failed (%s)", key[:12], e)
            return False
        victims: List[str] = []
        with _lock:
            m = _load_manifest(d)
            m["clock"] += 1
            entry = {"bytes": len(blob), "clock": m["clock"]}
            if kind:
                entry["kind"] = kind
            m["entries"][key] = entry
            victims = _evict_over_budget(d, m, protect=key)
            try:
                _commit_manifest(d, m)
            except OSError as e:
                log.warning("progcache: manifest commit failed (%s)", e)
            _stats["stores"] += 1
            _stats["evictions"] += len(victims)
    if victims:
        log.info("progcache: evicted %d entries over the %d-byte budget",
                 len(victims), byte_budget())
    return True


def touch(key: str):
    """Bump ``key``'s LRU clock (a hit, or a ladder retune keeping its
    bucket). Best-effort — advisory data only."""
    d = cache_dir()
    if d is None:
        return
    with _lock:
        m = _load_manifest(d)
        e = m["entries"].get(key)
        if e is None:
            return
        m["clock"] += 1
        e["clock"] = m["clock"]
        try:
            _commit_manifest(d, m)
        except OSError:
            pass


# --- persisted bucket ladders --------------------------------------------

def save_ladder(model_fp: str, buckets: Sequence[int]):
    """Persist a tuned bucket ladder for ``model_fp`` so a restarted
    server adopts it (and disk-loads exactly those programs) instead of
    rediscovering it from live traffic."""
    d = cache_dir()
    if d is None:
        return
    with _lock:
        m = _load_manifest(d)
        m.setdefault("ladders", {})[model_fp] = sorted(
            int(b) for b in buckets)
        try:
            os.makedirs(d, exist_ok=True)
            _commit_manifest(d, m)
        except OSError as e:
            log.warning("progcache: ladder save failed (%s)", e)


def load_ladder(model_fp: str) -> Optional[List[int]]:
    d = cache_dir()
    if d is None:
        return None
    with _lock:
        m = _load_manifest(d)
        lad = m.get("ladders", {}).get(model_fp)
    return [int(b) for b in lad] if lad else None


# --- introspection --------------------------------------------------------

def stats() -> Dict[str, int]:
    """Session counters (this process): hits/misses/fallbacks/stores/
    evictions."""
    with _lock:
        return dict(_stats)


def reset_stats():
    with _lock:
        for k in _stats:
            _stats[k] = 0


def bytes_in_use() -> int:
    """Bytes on disk in the active cache dir (from the manifest)."""
    d = cache_dir()
    if d is None:
        return 0
    with _lock:
        m = _load_manifest(d)
        total = sum(e.get("bytes", 0) for e in m["entries"].values())
        _bytes_by_dir[d] = total
        _refresh_kind_bytes(d, m)
    return total


def bytes_by_kind() -> Dict[str, int]:
    """Bytes on disk in the active cache dir split by entry ``kind``
    (``""`` collects entries stored before kinds existed)."""
    d = cache_dir()
    if d is None:
        return {}
    with _lock:
        m = _load_manifest(d)
        _refresh_kind_bytes(d, m)
        return dict(_bytes_by_dir_kind[d])
