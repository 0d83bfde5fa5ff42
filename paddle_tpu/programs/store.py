"""The program store: process-wide owner of JAX's persistent compilation
cache, with a content-addressed key that folds in paddle_tpu's own
semantic versions.

JAX's cache key covers the lowered HLO, compile options, and the
jax/jaxlib versions — but NOT this framework's op semantics: two
paddle_tpu builds whose `utils/op_version` registries differ can lower
byte-identical HLO for an op family whose serialized semantics changed
(the exact hazard the reference's op_version_registry exists for).  The
store therefore namespaces the cache directory by a fingerprint of
(paddle_tpu version, full op_version snapshot, jax version): a version
bump lands in a fresh subdirectory and recompiles — a stale artifact can
never be reused silently, and no artifact is ever invalidated in place.

Knobs (env, read at `ensure_enabled()` / import-time bootstrap):

- ``PDTPU_PROGRAM_CACHE_DIR``            base directory; unset = disabled
- ``JAX_COMPILATION_CACHE_DIR``          where set, the cache stays where
  JAX put it: the machine's owner placed it from outside (a directory
  that survives the process, or the job), so the store applies its
  thresholds and counters there and never names another directory
- ``PDTPU_PROGRAM_CACHE_MIN_COMPILE_S``  min compile seconds to persist
  (default 0: fleet cold-start wants even the small dispatch-cache
  programs — jax's own 1s default would skip them all)
- ``PDTPU_PROGRAM_CACHE_MAX_BYTES``      LRU cap for the cache dir
  (jax_compilation_cache_max_size; default unlimited)

Corrupt or unreadable entries are a warning + fresh compile, never a
crash (`jax_raise_persistent_cache_errors` is forced off).  Hit/miss
counters come from jax's own monitoring events and surface in
`stats()`, the metrics registry (``program_store_*`` series),
`observability.report()["program_store"]` and the gateway ``/healthz``.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Optional

__all__ = ["ProgramStore", "get_program_store", "enable", "disable",
           "ensure_enabled", "cache_fingerprint", "store_stats"]

_ENV_DIR = "PDTPU_PROGRAM_CACHE_DIR"
_ENV_JAX_DIR = "JAX_COMPILATION_CACHE_DIR"
_ENV_MIN_COMPILE = "PDTPU_PROGRAM_CACHE_MIN_COMPILE_S"
_ENV_MAX_BYTES = "PDTPU_PROGRAM_CACHE_MAX_BYTES"

# jax monitoring event names (jax/_src/compilation_cache.py)
_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_MISS = "/jax/compilation_cache/cache_misses"
_EV_REQ = "/jax/compilation_cache/compile_requests_use_cache"


def cache_fingerprint(paddle_version: Optional[str] = None,
                      op_versions: Optional[dict] = None,
                      jax_version: Optional[str] = None) -> str:
    """Content-address for the cache namespace: any change to the
    paddle_tpu version, ANY registered op version, or the jax version
    produces a different fingerprint (= a different subdirectory, = a
    guaranteed miss).  Arguments exist for tests; production callers use
    the live registries."""
    if paddle_version is None:
        from .. import version
        paddle_version = version.full_version
    if op_versions is None:
        from ..utils import op_version
        op_versions = op_version.snapshot()
    if jax_version is None:
        import jax
        jax_version = jax.__version__
    payload = json.dumps(
        {"paddle_tpu": paddle_version, "jax": jax_version,
         "op_versions": dict(sorted(op_versions.items()))},
        sort_keys=True).encode()
    return hashlib.sha256(payload).hexdigest()[:16]


class ProgramStore:
    """Singleton wrapper over jax's persistent compilation cache config
    (use `get_program_store()`; `enable`/`disable`/`stats` module
    functions proxy to it)."""

    def __init__(self):
        self._lock = threading.RLock()
        self._enabled = False
        self._base_dir: Optional[str] = None
        self._dir: Optional[str] = None
        self._fingerprint: Optional[str] = None
        self._saved_config: Optional[dict] = None
        # monitoring-fed counters (events keep firing process-wide; the
        # listener is registered once and gates on _enabled)
        self._hits = 0
        self._misses = 0
        self._requests = 0
        self._listener_registered = False
        self._collector_registered = False
        # disk-scan memo: stats() feeds /healthz and every Prometheus
        # scrape — an O(entries) directory walk per probe would make
        # readiness latency track cache size (bad on fleet-shared NFS)
        self._disk_cache = (0.0, 0, 0)  # (at, entries, bytes)

    # -- lifecycle ---------------------------------------------------------
    def enable(self, cache_dir: Optional[str] = None) -> Optional[str]:
        """Point every XLA compile in this process at the on-disk cache
        under `cache_dir` (or ``PDTPU_PROGRAM_CACHE_DIR``).  Returns the
        fingerprinted directory actually used, or None when no directory
        is configured.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the
        directory is JAX's own, whatever `cache_dir` says.  Re-enabling
        with the same dir is a no-op; enabling after compiles already
        happened works (jax's cache memoization is reset)."""
        with self._lock:
            base = cache_dir or os.environ.get(_ENV_DIR)
            if not base:
                return None
            import jax
            fp = cache_fingerprint()
            placed_outside = bool(os.environ.get(_ENV_JAX_DIR))
            target = (jax.config.jax_compilation_cache_dir if placed_outside
                      else os.path.join(base, f"v-{fp}"))
            if self._enabled and self._dir == target:
                return self._dir
            os.makedirs(target, exist_ok=True)
            if self._saved_config is None:
                self._saved_config = {
                    k: getattr(jax.config, k) for k in (
                        "jax_compilation_cache_dir",
                        "jax_persistent_cache_min_entry_size_bytes",
                        "jax_persistent_cache_min_compile_time_secs",
                        "jax_raise_persistent_cache_errors",
                        "jax_compilation_cache_max_size")}
            min_compile = float(os.environ.get(_ENV_MIN_COMPILE, "0") or 0)
            if not placed_outside:
                jax.config.update("jax_compilation_cache_dir", target)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              -1)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              min_compile)
            # corrupt artifact = warning + fresh compile, never a crash
            jax.config.update("jax_raise_persistent_cache_errors", False)
            max_bytes = os.environ.get(_ENV_MAX_BYTES)
            if max_bytes:
                jax.config.update("jax_compilation_cache_max_size",
                                  int(max_bytes))
            self._reset_jax_cache()
            self._base_dir = base
            self._dir = target
            self._fingerprint = fp
            self._enabled = True
            self._disk_cache = (0.0, 0, 0)
            self._register_listener()
            self._register_collector()
            return self._dir

    def disable(self):
        """Restore jax's prior cache config (tests; or turning the store
        off live)."""
        with self._lock:
            if not self._enabled:
                return
            import jax
            for k, v in (self._saved_config or {}).items():
                jax.config.update(k, v)
            self._saved_config = None
            self._enabled = False
            self._dir = None
            self._fingerprint = None
            self._reset_jax_cache()

    def ensure_enabled(self) -> bool:
        """Enable from the environment (the import-time bootstrap and
        the dispatch-cache miss hook): cheap no-op when
        ``PDTPU_PROGRAM_CACHE_DIR`` is unset."""
        with self._lock:
            if self._enabled:
                return True
            if not os.environ.get(_ENV_DIR):
                return False
            return self.enable() is not None

    @staticmethod
    def _reset_jax_cache():
        """jax memoizes is-the-cache-usable at the first compile; reset
        so enabling/disabling AFTER compiles have happened takes effect."""
        try:
            from jax._src import compilation_cache as _cc
            _cc.reset_cache()
        except Exception:
            pass

    # -- telemetry ---------------------------------------------------------
    def _register_listener(self):
        if self._listener_registered:
            return
        try:
            from jax._src import monitoring

            def _on_event(name, **kw):
                if not self._enabled:
                    return
                if name == _EV_HIT:
                    self._hits += 1
                elif name == _EV_MISS:
                    self._misses += 1
                    # a miss means a new entry was just written: drop the
                    # disk-scan memo so stats() reflects it immediately
                    self._disk_cache = (0.0, 0, 0)
                elif name == _EV_REQ:
                    self._requests += 1

            monitoring.register_event_listener(_on_event)
            self._listener_registered = True
        except Exception:
            pass  # older jax: stats degrade to entry counts only

    def _register_collector(self):
        if self._collector_registered:
            return
        try:
            from ..observability.metrics import get_registry

            def _collect():
                s = self.stats()
                return [
                    {"name": "program_store_enabled", "kind": "gauge",
                     "value": 1.0 if s["enabled"] else 0.0,
                     "help": "persistent program store active"},
                    {"name": "program_store_hits_total", "kind": "counter",
                     "value": s["hits"],
                     "help": "persistent-cache compile hits"},
                    {"name": "program_store_misses_total",
                     "kind": "counter", "value": s["misses"],
                     "help": "persistent-cache compile misses (written)"},
                    {"name": "program_store_entries", "kind": "gauge",
                     "value": s["entries"],
                     "help": "executables in the store"},
                    {"name": "program_store_bytes", "kind": "gauge",
                     "value": s["bytes"],
                     "help": "bytes on disk in the store"},
                ]

            get_registry().register_collector(_collect)
            self._collector_registered = True
        except Exception:
            pass

    _DISK_TTL_S = 2.0

    def stats(self) -> dict:
        """One snapshot: config + live hit/miss counters + disk usage.
        The directory scan is memoized for ~2s so health probes and
        metric scrapes stay O(1) against a large (possibly networked)
        cache dir."""
        import time
        with self._lock:
            at, entries, size = self._disk_cache
            now = time.monotonic()
            if self._dir and (now - at > self._DISK_TTL_S or at == 0.0):
                entries = size = 0
                try:
                    with os.scandir(self._dir) as it:
                        for e in it:
                            if e.name.endswith("-cache"):
                                entries += 1
                            try:
                                size += e.stat().st_size
                            except OSError:
                                pass
                except OSError:
                    pass
                self._disk_cache = (now, entries, size)
            elif not self._dir:
                entries = size = 0
            return {"enabled": self._enabled, "dir": self._dir,
                    "fingerprint": self._fingerprint,
                    "entries": entries, "bytes": size,
                    "hits": self._hits, "misses": self._misses,
                    "requests": self._requests}


_store = ProgramStore()


def get_program_store() -> ProgramStore:
    return _store


def enable(cache_dir: Optional[str] = None) -> Optional[str]:
    return _store.enable(cache_dir)


def disable():
    _store.disable()


def ensure_enabled() -> bool:
    return _store.ensure_enabled()


def store_stats() -> dict:
    return _store.stats()
