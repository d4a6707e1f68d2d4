"""Persistent AOT program cache — O(deserialize) cold start.

The deployment story (bind once, serve many — PAPER.md's Module/Executor
contract) assumes program construction is cheap relative to serving. It is
not: every new serve replica pays a full XLA compilation per shape bucket
at ``warmup()``, and the fused update engine recompiles its one-program
step at every train start — the single biggest obstacle to spawning
replicas on demand (serve/autoscale.py) and to fast elastic rejoin
(kvstore/elastic.py). This module is where a compiled program comes to
exist, and where it is kept across processes:

- **One build path** (:func:`build`): look in the cache, else lower and
  compile ahead of time, read the compiler's cost and memory analysis
  (:func:`analyze_compiled`, :data:`COST_FIELDS`), put the executable in
  the cache, and hand back the executable with the part of a
  ``compile_log`` entry that is the same at every site. ``serve/decode.py``,
  ``serve/engine.py``, ``optimizer/fused.py``, ``executor.py`` and
  ``gluon/block.py`` all build through it; nothing else in the package
  compiles a lowering.
- **One key derivation** (:func:`program_key`): every site derives its
  program identity through this one function — a :class:`ProgramKey`
  carries the (site, label) the ``obs/device.py`` registry files under
  plus a canonical SHA-256 ``digest`` over the program's statics
  (graph/optimizer fingerprint, avals, toggles). The same digest lands in
  ``compile_log`` entries, device cost records, and cache filenames, so
  the three surfaces can never key the same program differently.
- **Executable serialization** (:meth:`ProgramCache.put` / ``get``): a
  compiled ``jax.stages.Compiled`` is exported via
  ``jax.experimental.serialize_executable`` (XLA's own executable
  serialization — the deserialized program is the *same machine code*, so
  a cache hit is bitwise-identical to the compile it replaced). A
  backend that refuses executable export still has jax's persistent
  *compilation* cache under it (``mxnet_tpu/__init__.py`` places it) —
  slower than a deserialize but it skips XLA optimization on re-compiles.
- **Never a wrong program**: every entry embeds an environment
  fingerprint (backend platform + device kind + topology + jax/jaxlib
  versions + an ``mxnet_tpu`` source-tree content hash) checked before
  deserialization. A stale, foreign-platform, truncated, or CRC-corrupt
  entry is a *structured MISS/REJECT* — counted
  (``progcache.{hit,miss,reject,write}`` metrics + obs events) and
  degraded to a plain compile, never a crash, never a wrong program.
- **Crash-safe writes**: the ``checkpoint/`` idiom — temp + fsync +
  rename, per-entry CRC32, keep-last-N GC (``MXNET_PROGCACHE_KEEP``).

Activation: ``MXNET_PROGCACHE_DIR=<dir>`` (or ``MXNET_PROGCACHE=1`` with
the default ``<checkout>/.mxnet_progcache``) arms the process-global
cache; ``MXNET_PROGCACHE=0`` vetoes it even with a dir set. Serving
artifacts can also ship their executables: ``serve.ship_programs`` writes
an engine's compiled buckets into a ``programs/`` payload next to the
artifact and ``serve.load`` warms from it (docs/PERFORMANCE.md "Program
cache and cold start").
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import struct
import threading
import time
from typing import Any, Dict, NamedTuple, Optional

from .base import checkout_path
from .checkpoint.atomic import atomic_write_bytes, crc32_bytes

__all__ = ["ProgramKey", "ProgramCache", "CacheEntry", "program_key",
           "env_fingerprint", "code_fingerprint", "active", "cache",
           "configure", "build", "analyze_compiled", "COST_FIELDS",
           "serialize_compiled", "default_dir", "reset"]

# entry format version — bump on any layout/semantic change so old caches
# read as structured rejects, not parse errors
_MAGIC = b"MXPROG1\n"
_SCHEMA = 2  # 2: the payload records the program's device ids

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


# ---------------------------------------------------------------------------
# key derivation — THE one place a program's identity is computed
# ---------------------------------------------------------------------------

class ProgramKey(NamedTuple):
    """A program's identity: the (site, label) the device-plane registry
    files cost records under, plus the canonical digest over its statics.
    Built only by :func:`program_key` so every surface derives identically.
    """
    site: str
    label: str
    digest: str


def _canon(obj) -> Any:
    """Canonicalize arbitrary static key parts into a deterministic,
    JSON-able structure. Types become qualified names, mappings sort by
    key, sets sort; anything else falls back to ``repr`` (tuples of
    primitives — the aval idiom — repr deterministically)."""
    if isinstance(obj, type):
        return f"{obj.__module__}.{obj.__qualname__}"
    if isinstance(obj, (str, int, bool)) or obj is None:
        return obj
    if isinstance(obj, float):
        return repr(obj)  # repr(f) roundtrips; json would re-round
    if isinstance(obj, bytes):
        return hashlib.sha256(obj).hexdigest()
    if isinstance(obj, dict):
        return {"__map__": sorted((str(k), _canon(v))
                                  for k, v in obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_canon(x) for x in obj]
    if isinstance(obj, (set, frozenset)):
        return {"__set__": sorted(repr(_canon(x)) for x in obj)}
    return repr(obj)


def program_key(site: str, label: str, statics: Any = ()) -> ProgramKey:
    """Derive a program's :class:`ProgramKey` from its compile statics.

    ``site``/``label`` follow the device-plane registry convention
    ("serve"/"bucket32", "update"/"Adam", ...); ``statics`` is everything
    that determines the traced program short of traced-argument *values*
    (graph fingerprint, avals, static hyperparameters, toggles). Two call
    sites passing equal statics get equal digests in any process."""
    blob = json.dumps([_SCHEMA, site, label, _canon(statics)],
                      sort_keys=True, separators=(",", ":"))
    return ProgramKey(site, label,
                      hashlib.sha256(blob.encode("utf-8")).hexdigest())


# ---------------------------------------------------------------------------
# environment fingerprint — when ANY of this drifts, entries MISS
# ---------------------------------------------------------------------------

_code_fp_cache: list = [None]
_env_fp_cache: list = [None]
# reentrant: env_fingerprint() computes code_fingerprint() under it
_fp_lock = threading.RLock()


def code_fingerprint() -> str:
    """Content hash over every ``mxnet_tpu/**/*.py`` source file. Programs
    are traced from this package's code, so a source change anywhere in it
    invalidates the cache — coarse, but the failure mode of a finer map
    (a stale program served after a lowering edit) is a silently wrong
    model. Computed once per process."""
    if _code_fp_cache[0] is not None:
        return _code_fp_cache[0]
    with _fp_lock:
        if _code_fp_cache[0] is not None:
            return _code_fp_cache[0]
        root = os.path.dirname(os.path.abspath(__file__))
        h = hashlib.sha256()
        for dirpath, dirnames, filenames in sorted(os.walk(root)):
            dirnames.sort()
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                h.update(os.path.relpath(path, root).encode())
                try:
                    with open(path, "rb") as f:
                        h.update(f.read())
                except OSError:
                    h.update(b"<unreadable>")
        _code_fp_cache[0] = h.hexdigest()
        return _code_fp_cache[0]


def env_fingerprint() -> Dict[str, Any]:
    """The compatibility envelope of a serialized executable: backend
    platform, device kind, topology, jax/jaxlib versions, XLA topology
    flags, and the package source hash. Any mismatch on read is a
    structured reject — ``deserialize_and_load`` on a foreign platform
    would abort the process, and a version skew could execute stale HLO.
    """
    if _env_fp_cache[0] is not None:
        return dict(_env_fp_cache[0])
    with _fp_lock:
        if _env_fp_cache[0] is not None:
            return dict(_env_fp_cache[0])
        import jax
        import jaxlib

        try:
            devs = jax.devices()
            kind = devs[0].device_kind if devs else "?"
            ndev = len(devs)
        except Exception:  # lint-ok: fingerprint must never raise
            kind, ndev = "?", 0
        fp = {
            "schema": _SCHEMA,
            "platform": jax.default_backend(),
            "device_kind": str(kind),
            "num_devices": int(ndev),
            "process_count": int(getattr(jax, "process_count", lambda: 1)()),
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
            # jax config knobs that shape compiled numerics: a writer with
            # x64 on or a different matmul precision would otherwise hand
            # a fingerprint-matching reader a numerically different
            # program than the one it would compile itself — breaking the
            # bitwise serve-vs-predict contract on hits
            "x64": bool(getattr(jax.config, "jax_enable_x64", False)),
            "matmul_precision": str(getattr(
                jax.config, "jax_default_matmul_precision", None)),
            "code": code_fingerprint(),
        }
        _env_fp_cache[0] = fp
        return dict(fp)


# ---------------------------------------------------------------------------
# serialization helpers
# ---------------------------------------------------------------------------

def _program_devices(compiled) -> list:
    """The devices a ``jax.stages.Compiled`` was compiled for, in the
    executable's own order."""
    return list(compiled._executable.xla_executable.local_devices())


def serialize_compiled(compiled) -> Optional[bytes]:
    """Export a ``jax.stages.Compiled`` to bytes (pickle of XLA's
    serialized executable, the call signature pytrees, and the ids of the
    devices it was compiled for), or None when the backend refuses
    export — the program then recompiles through jax's persistent
    compilation cache."""
    try:
        from jax.experimental import serialize_executable as _se

        payload, in_tree, out_tree = _se.serialize(compiled)
        device_ids = [d.id for d in _program_devices(compiled)]
        buf = io.BytesIO()
        pickle.dump((payload, in_tree, out_tree, device_ids), buf,
                    protocol=pickle.HIGHEST_PROTOCOL)
        return buf.getvalue()
    except Exception:  # lint-ok: export support is backend-dependent
        return None


def _deserialize_compiled(blob: bytes):
    """Load a serialized program back onto the devices it was compiled
    for. ``deserialize_and_load`` defaults to EVERY device of the backend,
    which makes a one-device program unrunnable on a multi-device host
    ("expected 8 shards, got 1") — hence the recorded ids."""
    import jax
    from jax.experimental import serialize_executable as _se

    payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

class CacheEntry(NamedTuple):
    """A successful ``get``: the loaded executable + the entry's stored
    metadata (the compile-time cost record, bucket, timestamps...)."""
    executable: Any
    meta: Dict[str, Any]


def _obs_count(name: str, **attrs) -> None:
    # metrics/events only when telemetry records; the cache's own stats
    # dict counts unconditionally (STATS replies and tests read those)
    from . import obs

    if obs.enabled():
        obs.inc(f"progcache.{name}")
        if name in ("reject", "write", "export_refused"):
            obs.event(f"progcache.{name}", **attrs)


class ProgramCache:
    """One cache directory of serialized executables.

    Layout: ``<root>/<digest>.mxprog``, each file::

        MXPROG1\\n | u32 header_len | header json | u64 payload_len |
        payload (pickled serialized executable) | u32 crc32(all prior)

    The header carries the :class:`ProgramKey`, the writer's
    :func:`env_fingerprint`, and caller metadata (cost record, bucket).
    Writes are atomic (temp + fsync + rename); reads verify magic, CRC,
    digest, and fingerprint *before* unpickling — a mismatch on any is a
    counted reject, and the caller compiles as if the entry never existed.
    """

    def __init__(self, root: str, keep: Optional[int] = None,
                 durable: bool = True):
        self.root = str(root)
        if keep is None:
            from .obs._env import env_int

            keep = env_int("MXNET_PROGCACHE_KEEP", 128)
        self.keep = int(keep)
        self.durable = bool(durable)
        os.makedirs(self.root, exist_ok=True)
        self._lock = threading.Lock()
        self.stats: Dict[str, int] = {"hit": 0, "miss": 0, "reject": 0,
                                      "write": 0, "export_refused": 0}

    def _count(self, name: str, **attrs) -> None:
        with self._lock:
            self.stats[name] = self.stats.get(name, 0) + 1
        _obs_count(name, **attrs)

    def _path(self, digest: str) -> str:
        return os.path.join(self.root, f"{digest}.mxprog")

    # -- read ----------------------------------------------------------
    def _read_entry(self, path: str, digest: str):
        """Parse + verify one entry file. Returns (header, payload) or a
        string reject reason."""
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return "unreadable"
        if len(raw) < len(_MAGIC) + 4 + 8 + 4 \
                or not raw.startswith(_MAGIC):
            return "bad_magic"
        body, crc_bytes = raw[:-4], raw[-4:]
        if crc32_bytes(body) != struct.unpack("<I", crc_bytes)[0]:
            return "crc_mismatch"
        off = len(_MAGIC)
        (hlen,) = struct.unpack_from("<I", body, off)
        off += 4
        try:
            header = json.loads(body[off:off + hlen].decode("utf-8"))
        except (ValueError, UnicodeDecodeError):
            return "bad_header"
        off += hlen
        (plen,) = struct.unpack_from("<Q", body, off)
        off += 8
        payload = body[off:off + plen]
        if len(payload) != plen:
            return "truncated"
        if header.get("key", {}).get("digest") != digest:
            return "digest_mismatch"
        if header.get("env") != env_fingerprint():
            return "env_mismatch"
        return header, payload

    def get(self, key: ProgramKey) -> Optional[CacheEntry]:
        """Load the executable for ``key``. A missing file is a counted
        miss; a present-but-unusable one (corrupt, truncated, foreign
        platform, stale code, deserialize failure) is a counted reject —
        both return None and the caller compiles normally."""
        path = self._path(key.digest)
        if not os.path.exists(path):
            self._count("miss")
            return None
        res = self._read_entry(path, key.digest)
        if isinstance(res, str):
            self._count("reject", reason=res, site=key.site,
                        label=key.label)
            return None
        header, payload = res
        try:
            executable = _deserialize_compiled(payload)
        except Exception as e:  # lint-ok: a bad blob degrades to compile
            self._count("reject", reason=f"deserialize:{type(e).__name__}",
                        site=key.site, label=key.label)
            return None
        self._count("hit")
        # touch so keep-last-N GC ranks by USE recency, not write time
        try:
            os.utime(path, None)
        except OSError:
            pass
        return CacheEntry(executable, header.get("meta") or {})

    # -- write ---------------------------------------------------------
    def put(self, key: ProgramKey, compiled,
            meta: Optional[dict] = None) -> bool:
        """Serialize + commit one executable. Returns False when the
        backend refuses export. Concurrent writers of the same key are
        safe: rename is atomic and both wrote identical content.

        Every blob is round-trip verified (``deserialize_and_load``)
        before it is published: XLA:CPU's JIT dedupes identical kernels
        process-wide, so an executable compiled after a kernel-hash twin
        can REFERENCE kernels it does not embed — its serialization loads
        nowhere, not even in the writer process. Deserialization builds a
        fresh function library from the blob alone, so the verify catches
        exactly the entries a cold reader would have to reject — and the
        loaded program must sit on the devices the original was compiled
        for, or its first call would fail. A blob that fails either check
        counts as ``export_refused`` instead of poisoning the dir."""
        blob = serialize_compiled(compiled)
        if blob is not None:
            try:
                loaded = _deserialize_compiled(blob)
                if _program_devices(loaded) != _program_devices(compiled):
                    blob = None
            except Exception:  # lint-ok: unloadable export = refused export
                blob = None
        if blob is None:
            self._count("export_refused", site=key.site, label=key.label)
            return False
        header = json.dumps(
            {"key": key._asdict(), "env": env_fingerprint(),
             "meta": meta or {}, "created": time.time()},
            sort_keys=True).encode("utf-8")
        body = b"".join([_MAGIC, struct.pack("<I", len(header)), header,
                         struct.pack("<Q", len(blob)), blob])
        data = body + struct.pack("<I", crc32_bytes(body))
        try:
            atomic_write_bytes(self._path(key.digest), data,
                               durable=self.durable)
        except OSError:
            return False
        self._count("write", site=key.site, label=key.label,
                    bytes=len(data))
        self.gc()
        return True

    # -- GC ------------------------------------------------------------
    def gc(self) -> int:
        """Keep the ``keep`` most recently used entries (by mtime — reads
        touch); drop the rest. Returns how many were removed."""
        if self.keep <= 0:
            return 0
        try:
            entries = [e for e in os.listdir(self.root)
                       if e.endswith(".mxprog")]
        except OSError:
            return 0
        if len(entries) <= self.keep:
            return 0
        stamped = []
        for e in entries:
            try:
                stamped.append((os.path.getmtime(
                    os.path.join(self.root, e)), e))
            except OSError:
                continue  # a concurrent GC got it first
        stamped.sort(reverse=True)
        removed = 0
        for _, e in stamped[self.keep:]:
            try:
                os.unlink(os.path.join(self.root, e))
                removed += 1
            except OSError:
                pass
        return removed

    def entries(self) -> int:
        try:
            return sum(1 for e in os.listdir(self.root)
                       if e.endswith(".mxprog"))
        except OSError:
            return 0


# ---------------------------------------------------------------------------
# the build path — THE one place a compiled program comes to exist
# ---------------------------------------------------------------------------

# what a compiled program says of itself; the field order is the
# compile_log/report schema, keep stable
COST_FIELDS = ("flops", "bytes_accessed", "argument_bytes", "output_bytes",
               "temp_bytes", "generated_code_bytes", "alias_bytes",
               "peak_hbm_bytes")


def analyze_compiled(compiled) -> dict:
    """Extract the cost/memory record from a ``jax.stages.Compiled``.
    Missing analyses (backend-dependent) just leave fields at 0 — the
    record is always structurally complete."""
    cost: dict = {k: 0 for k in COST_FIELDS}
    try:
        ca = compiled.cost_analysis()
        if ca:
            cost["flops"] = int(ca.get("flops", 0) or 0)
            cost["bytes_accessed"] = int(ca.get("bytes accessed", 0) or 0)
    except Exception:  # lint-ok: cost analysis is best-effort by contract
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            arg = int(getattr(ma, "argument_size_in_bytes", 0))
            out = int(getattr(ma, "output_size_in_bytes", 0))
            tmp = int(getattr(ma, "temp_size_in_bytes", 0))
            code = int(getattr(ma, "generated_code_size_in_bytes", 0))
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
            cost.update(argument_bytes=arg, output_bytes=out, temp_bytes=tmp,
                        generated_code_bytes=code, alias_bytes=alias,
                        # donated buffers alias an argument into an output;
                        # counting both would double the footprint
                        peak_hbm_bytes=max(arg + out + tmp + code - alias, 0))
    except Exception:  # lint-ok: memory analysis is best-effort by contract
        pass
    return cost


def build(jitted, args: tuple, *, key: Optional[ProgramKey] = None,
          cache: Optional["ProgramCache"] = None,
          meta: Optional[dict] = None, kwargs: Optional[dict] = None):
    """Build ``jitted`` (a ``jax.jit`` wrapper) for the example ``args``
    ahead of time: ``(executable, entry)``.

    With a ``cache`` and a ``key``, ``cache.get(key)`` first: on a hit the
    executable is the deserialized one (the same machine code an earlier
    process compiled) and its cost the :data:`COST_FIELDS` the writer left
    in the entry's metadata. Otherwise ``jitted`` is lowered and compiled,
    the cost read from the compiled program (:func:`analyze_compiled`),
    and the executable put in the cache with ``{**cost, **meta}``. The
    caller keeps ``executable`` for this signature: ONE compile is measured
    and run.

    ``entry`` is the part of a ``compile_log`` line that is the same at
    every site — ``cache_hit``, ``program_key`` (the digest, when keyed)
    and the cost fields; a site adds its own keys and appends. With ``obs``
    on, the cost is also filed under the key's (site, label) through
    ``obs.device.record`` (the ``device.compile`` event and gauges).

    Errors have two meanings, both kept here. A compile error is the
    caller's to see: where the program must exist ahead of time
    (``DecodeEngine``, any site with a cache armed) there is nothing to
    fall back to, and where the build is only for the record the jit
    wrapper would run the same compiler. A failure to *lower* (an exotic
    backend, a lowering restriction) leaves the caller on its jit wrapper:
    ``jitted`` itself comes back, with an entry that has no cost.
    """
    entry: Dict[str, Any] = {"cache_hit": False}
    keyed = cache is not None and key is not None
    if key is not None:
        entry["program_key"] = key.digest
    cached = cache.get(key) if keyed else None
    if cached is not None:
        entry["cache_hit"] = True
        executable = cached.executable
        cost = {k: cached.meta[k] for k in COST_FIELDS if k in cached.meta}
    else:
        try:
            lowered = jitted.lower(*args, **(kwargs or {}))
        except Exception:  # lint-ok: the jit wrapper decides, never raise
            return jitted, entry
        executable = lowered.compile()
        cost = analyze_compiled(executable)
        if keyed:
            cache.put(key, executable, meta={**cost, **(meta or {})})
    entry.update(cost)
    if cost and key is not None:
        from . import obs

        if obs.enabled():
            obs.device.record(key.site, key.label,
                              dict(cost, program_key=key.digest))
    return executable, entry


# ---------------------------------------------------------------------------
# process-global activation (env-driven; engines default to this)
# ---------------------------------------------------------------------------

_global: list = [None, False]  # [ProgramCache|None, resolved?]
_global_lock = threading.Lock()


def default_dir() -> str:
    return checkout_path(".mxnet_progcache")


def active() -> bool:
    """Is the process-global persistent cache armed?
    ``MXNET_PROGCACHE=0`` vetoes; ``MXNET_PROGCACHE_DIR`` (or
    ``MXNET_PROGCACHE=1`` with the default dir) arms."""
    env = os.environ.get("MXNET_PROGCACHE", "").lower()
    if env in _FALSE:
        return False
    return env in _TRUE or bool(os.environ.get("MXNET_PROGCACHE_DIR"))


def cache() -> Optional[ProgramCache]:
    """The process-global :class:`ProgramCache`, or None when inactive.
    Resolved from the environment on first use; :func:`configure`
    overrides programmatically."""
    if not active():
        return None
    if _global[1]:
        return _global[0]
    with _global_lock:
        if not _global[1]:
            root = os.environ.get("MXNET_PROGCACHE_DIR") or default_dir()
            try:
                _global[0] = ProgramCache(root)
            except OSError:
                _global[0] = None  # unwritable dir: run uncached
            _global[1] = True
    return _global[0]


def configure(directory: Optional[str], keep: Optional[int] = None
              ) -> Optional[ProgramCache]:
    """Arm (or disarm with None) the process-global cache in code — the
    env-free path tools and tests use."""
    with _global_lock:
        if directory is None:
            _global[0], _global[1] = None, True
            os.environ["MXNET_PROGCACHE"] = "0"
            return None
        os.environ.pop("MXNET_PROGCACHE", None)
        os.environ["MXNET_PROGCACHE_DIR"] = str(directory)
        _global[0] = ProgramCache(str(directory), keep=keep)
        _global[1] = True
        return _global[0]


def reset() -> None:
    """Forget the resolved global cache (tests; the next :func:`cache`
    re-reads the environment)."""
    with _global_lock:
        _global[0], _global[1] = None, False
