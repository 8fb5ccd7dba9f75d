"""Byte-accounted LRU block-cache registry — the bufmgr analog.

Reference parity: the shared buffer pool (src/backend/storage/buffer/
bufmgr.c) gives every read path one bounded, recency-evicting cache with
hit/miss/eviction accounting. Our reproduction grew six ad-hoc dict caches
(raw chunks, host predicates, raw codes, packed prefixes, deletion masks,
staged device inputs), each with its own "pop the first key" pseudo-
eviction — which evicts INSERTION order, not recency, and none of which
bound actual bytes. This module replaces all of them:

  - ``CacheRegistry`` owns one global byte budget (the ``scan_cache_limit_mb``
    GUC, read live from the wired settings) shared by every named cache.
  - ``BlockCache`` is one named member: an OrderedDict in recency order
    (every hit moves the entry to MRU), so the registry's eviction scan can
    find the GLOBAL least-recently-used entry by comparing each cache's
    head tick.
  - Entries carry their byte size (``nbytes_of`` estimates when the caller
    doesn't know) and an optional manifest version tag;
    ``invalidate_versions(keep)`` drops every tagged entry from another
    version — the CdbComponentDatabases/relcache invalidation analog for
    a manifest bump (DML, index build, expansion).
  - ``scan_cache_hit`` / ``scan_cache_miss`` / ``scan_cache_evict``
    counters land in the runtime.logger registry so EXPLAIN ANALYZE and
    tests can assert cache behavior without wall clocks.

Thread safety: one registry RLock covers every operation — the staging
thread pool hits these caches from many threads at once.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

from greengage_tpu.runtime import lockdebug, memaccount, overload
from greengage_tpu.runtime.logger import counters

MISS = object()   # sentinel distinguishing "absent" from a cached None

DEFAULT_LIMIT_MB = 1024


def nbytes_of(value) -> int:
    """Best-effort byte estimate of a cached value (numpy / jax arrays
    report exactly; containers sum their members; scalars cost a token)."""
    nb = getattr(value, "nbytes", None)
    if nb is not None:
        try:
            return int(nb) + 64
        except (TypeError, ValueError):
            pass
    if isinstance(value, (tuple, list)):
        return 64 + sum(nbytes_of(v) for v in value)
    if isinstance(value, dict):
        return 64 + sum(nbytes_of(v) for v in value.values())
    if isinstance(value, (str, bytes)):
        return 64 + len(value)
    return 64


class BlockCache:
    """One named cache inside a registry. All mutation happens under the
    registry lock; entries are (value, nbytes, version, tick)."""

    def __init__(self, registry: "CacheRegistry", name: str):
        self.registry = registry
        self.name = name
        # access-witnessed under GGTPU_RACE_DEBUG: every touch must hold
        # the registry lock (docs/ANALYSIS.md "Race analysis")
        self._d: OrderedDict = lockdebug.shared(OrderedDict(),
                                                f"blockcache.{name}._d")
        self.bytes = 0

    # -- reads ----------------------------------------------------------
    def get(self, key, default=None):
        reg = self.registry
        with reg._lock:
            ent = self._d.get(key)
            if ent is None:
                counters.inc("scan_cache_miss")
                return default
            self._d.move_to_end(key)
            ent[3] = reg._next_tick()
            counters.inc("scan_cache_hit")
            return ent[0]

    def peek(self, key, default=None):
        """Read without touching recency or hit/miss counters."""
        with self.registry._lock:
            ent = self._d.get(key)
            return default if ent is None else ent[0]

    def __contains__(self, key) -> bool:
        with self.registry._lock:
            return key in self._d

    def __len__(self) -> int:
        with self.registry._lock:
            return len(self._d)

    def keys(self) -> list:
        with self.registry._lock:
            return list(self._d.keys())

    # -- writes ---------------------------------------------------------
    def put(self, key, value, nbytes: int | None = None,
            version: int | None = None) -> None:
        nb = nbytes_of(value) if nbytes is None else int(nbytes) + 64
        reg = self.registry
        with reg._lock:
            old = self._d.pop(key, None)
            if old is not None:
                self.bytes -= old[1]
                reg._total -= old[1]
            if nb > reg.limit_bytes():
                # an entry bigger than the WHOLE budget can never be
                # resident: refuse it outright rather than evicting every
                # other cache's warm state on its behalf and then evicting
                # it anyway
                return
            self._d[key] = [value, nb, version, reg._next_tick()]
            self.bytes += nb
            reg._total += nb
            reg._evict_to_fit()
        # memory accounting (runtime/memaccount.py): attribute the bytes
        # this statement INSERTED into the shared cache to its
        # 'blockcache' owner — charged OUTSIDE the registry lock so the
        # account lock never nests under it (lock-order hygiene). Pool
        # threads reach here bound to the statement's account.
        memaccount.charge("blockcache", nb, item=self.name)

    def pop(self, key, default=None):
        with self.registry._lock:
            ent = self._d.pop(key, None)
            if ent is None:
                return default
            self.bytes -= ent[1]
            self.registry._total -= ent[1]
            return ent[0]

    def clear(self) -> None:
        with self.registry._lock:
            self.registry._total -= self.bytes
            self.bytes = 0
            self._d.clear()

    def drop(self, pred) -> int:
        """Remove entries whose KEY satisfies ``pred``; -> count removed."""
        with self.registry._lock:
            victims = [k for k in self._d if pred(k)]
            for k in victims:
                ent = self._d.pop(k)
                self.bytes -= ent[1]
                self.registry._total -= ent[1]
            return len(victims)


class CacheRegistry:
    """Shared byte budget + global-LRU eviction over named BlockCaches."""

    def __init__(self, limit_mb: int | None = None):
        self._lock = lockdebug.named(threading.RLock(),
                                     "blockcache.registry._lock")
        self._caches: dict[str, BlockCache] = {}
        self._tick = 0
        self._total = 0
        self._limit_mb = limit_mb
        # wired by the session (Database.__init__); read live so
        # SET scan_cache_limit_mb applies to the next eviction decision
        self.settings = None

    def cache(self, name: str) -> BlockCache:
        with self._lock:
            c = self._caches.get(name)
            if c is None:
                c = self._caches[name] = BlockCache(self, name)
            return c

    def limit_bytes(self) -> int:
        mb = None
        if self.settings is not None:
            mb = getattr(self.settings, "scan_cache_limit_mb", None)
        if mb is None:
            mb = self._limit_mb if self._limit_mb is not None \
                else DEFAULT_LIMIT_MB
        base = max(int(mb), 1) << 20
        # memory-pressure brownout (runtime/overload.py): under device
        # pressure the shared budget shrinks by the brownout cache
        # factor — read live, so SET and state transitions apply to the
        # next eviction decision, exactly like the GUC itself
        factor = overload.CONTROLLER.cache_factor()
        if factor >= 1.0:
            return base
        return max(int(base * factor), 1 << 20)

    def evict_to_fit(self) -> None:
        """Public eviction-to-budget pass: applied on a brownout
        transition edge so the shrunken budget frees bytes NOW instead
        of waiting for the next insert."""
        with self._lock:
            self._evict_to_fit()

    @property
    def total_bytes(self) -> int:
        with self._lock:
            return self._total

    def _next_tick(self) -> int:
        self._tick += 1
        return self._tick

    def _evict_to_fit(self) -> None:
        """Evict the GLOBALLY least-recent entry until under budget. Each
        cache's OrderedDict head is its own LRU, so the global LRU is the
        minimum head tick across caches — O(#caches) per eviction."""
        limit = self.limit_bytes()
        while self._total > limit:
            best = None
            best_cache = None
            for c in self._caches.values():
                if not c._d:
                    continue
                k = next(iter(c._d))
                tick = c._d[k][3]
                if best is None or tick < best[1]:
                    best = (k, tick)
                    best_cache = c
            if best_cache is None:
                return   # nothing left to evict
            ent = best_cache._d.pop(best[0])
            best_cache.bytes -= ent[1]
            self._total -= ent[1]
            counters.inc("scan_cache_evict")

    def invalidate_versions(self, keep_version: int,
                            by_cache: dict | None = None) -> int:
        """Drop every version-tagged entry from another manifest version
        (the manifest-bump invalidation); untagged entries — immutable
        committed files — stay. -> count removed (and, a cache's name,
        in ``by_cache``: what a write cost the readers after it)."""
        removed = 0
        with self._lock:
            for c in self._caches.values():
                victims = [k for k, ent in c._d.items()
                           if ent[2] is not None and ent[2] != keep_version]
                for k in victims:
                    ent = c._d.pop(k)
                    c.bytes -= ent[1]
                    self._total -= ent[1]
                removed += len(victims)
                if by_cache is not None and victims:
                    by_cache[c.name] = len(victims)
        return removed

    def clear(self) -> None:
        with self._lock:
            for c in self._caches.values():
                c._d.clear()
                c.bytes = 0
            self._total = 0

    def stats(self) -> dict:
        with self._lock:
            return {
                "total_bytes": self._total,
                "limit_bytes": self.limit_bytes(),
                "caches": {n: {"entries": len(c._d), "bytes": c.bytes}
                           for n, c in self._caches.items()},
            }
