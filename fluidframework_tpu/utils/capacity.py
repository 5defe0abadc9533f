"""Capacity plane (ISSUE 19): resident-doc census, device-memory
accounting, idle-age tracking.

ROADMAP items 1 (row migration) and 3 (doc eviction / lazy hydration)
both key off a signal that did not exist until this module: what a
resident doc *costs*, where the bytes live (host heap vs device HBM),
and how long each doc has been idle. The reference architecture
presumes exactly this — Routerlicious spins per-doc ordering state up
and down, which requires knowing what "down" would reclaim.

Three cooperating pieces:

* :class:`CapacityLedger` — a process-wide registry (module singleton
  :data:`LEDGER`, same pattern as ``telemetry.REGISTRY``) that
  memory-owning components register *pull providers* against. A
  provider is a zero-arg callable returning a :func:`report` dict
  (host bytes by category, device bytes, resident-doc count, optional
  per-doc heavy hitters). Registration holds weak references only —
  engines are born and die by the hundreds in tests and the ledger
  must never keep one alive. Components keep O(1) *incremental*
  byte counters at their growth points (interner payload appends,
  oplog tail appends, dedup inserts) so a census is a cheap walk of
  precomputed numbers, never an O(heap) traversal.

* device census — :func:`device_census` walks ``jax.live_arrays()``
  for the ground-truth HBM/backend-buffer total (the acceptance test
  pins ledger device totals to this number *exactly*) and reads the
  global pjit compile-cache occupancy from jax's C++ pjit caches
  (entry counts; jaxlib does not expose per-entry bytes — reported as
  ``None``, never guessed).

* :class:`IdleAgeTracker` — a monotonic last-touch clock per doc row.
  Both ingress doors touch it from their drain passes with ONE
  vectorized scatter per drained window (``last[rows] = now``) — no
  per-op cost. The census turns the clock into an idle-age histogram
  plus top-K coldest rows; coldest rows report the exact stamp of
  their last touch so "untouched since tick T" is provable.

Importing this module installs two flight-recorder dump-context
providers (``capacity_census`` and ``metrics_snapshot``) so every
crash/SLO-breach dump carries the memory picture for offline
forensics.
"""

from __future__ import annotations

import sys
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import flight_recorder as _flight
from . import telemetry as _telemetry

__all__ = [
    "CapacityLedger", "IdleAgeTracker", "LEDGER",
    "device_census", "compile_cache_stats", "device_nbytes",
    "report", "str_nbytes", "ndarray_nbytes", "interner_nbytes",
    "dict_nbytes", "list_nbytes", "record_nbytes",
    "idle_age_histogram",
]


# --------------------------------------------------------------------------
# host-side sizing helpers
# --------------------------------------------------------------------------
# Calibrated against CPython 3.10 x86-64 with tracemalloc (the census
# accuracy test holds the ledger within 15% of a tracemalloc delta, so
# these are measured amortized costs, not guesses).

#: amortized bytes per list slot (pointer + growth slack)
LIST_SLOT_BYTES = 8
#: amortized dict-table bytes per entry, EXCLUDING key/value objects
DICT_ENTRY_BYTES = 52
#: dict entry including two boxed ints (seq→seq maps, row caches)
INT_DICT_ENTRY_BYTES = 108
#: OrderedDict entry incl. boxed int key + small tuple value (the dedup
#: ledger's per-client window rows)
ODICT_ENTRY_BYTES = 195
#: empty OrderedDict container (one per (doc, client) dedup key)
ODICT_EMPTY_BYTES = 137
#: numpy array object header + base overhead beyond ``.nbytes``
NDARRAY_OVERHEAD_BYTES = 128
#: python object header of a small dataclass/record instance
RECORD_OVERHEAD_BYTES = 64


def str_nbytes(s: str) -> int:
    """Host bytes of one str object (exact for materialized strings)."""
    return sys.getsizeof(s)


def ndarray_nbytes(a: Any) -> int:
    """Host bytes of one numpy array: payload + object overhead."""
    nb = getattr(a, "nbytes", None)
    if nb is None:
        return 0
    return int(nb) + NDARRAY_OVERHEAD_BYTES


def list_nbytes(n_slots: int) -> int:
    """Amortized container bytes of a list with ``n_slots`` elements
    (element objects are charged separately by their own estimators)."""
    return 56 + LIST_SLOT_BYTES * int(n_slots)


def dict_nbytes(n_entries: int, per_entry: int = DICT_ENTRY_BYTES) -> int:
    """Amortized bytes of a dict with ``n_entries`` entries."""
    return 64 + per_entry * int(n_entries)


def interner_nbytes(n_entries: int, payload_bytes: int) -> int:
    """An interner table: id→payload list + payload→id dict around
    ``payload_bytes`` of accounted payload objects."""
    n = int(n_entries)
    return int(payload_bytes) + list_nbytes(n) + dict_nbytes(n)


def record_nbytes(rec: Any) -> int:
    """Host bytes of one oplog in-memory tail record.

    Counts numpy plane payloads (the dominant cost of columnar
    records) plus a constant object overhead. Deliberately does NOT
    walk str fields: sequenced-message texts are shared references
    into the interner payload table, which already charges them — a
    second charge here would double-count against tracemalloc."""
    total = RECORD_OVERHEAD_BYTES
    d = getattr(rec, "__dict__", None)
    if d is None and hasattr(rec, "__dataclass_fields__"):
        d = {f: getattr(rec, f, None) for f in rec.__dataclass_fields__}
    if d:
        total += dict_nbytes(len(d))
        for v in d.values():
            if isinstance(v, np.ndarray):
                total += ndarray_nbytes(v)
    return total


# --------------------------------------------------------------------------
# device census
# --------------------------------------------------------------------------

def device_nbytes(tree: Any) -> int:
    """Device-buffer bytes of one jax pytree (a store's ``state``):
    the sum of ``.nbytes`` over its jax-array leaves. Matches what
    ``jax.live_arrays()`` reports for the same buffers."""
    try:
        import jax
    except Exception:                                  # pragma: no cover
        return 0
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            total += int(leaf.nbytes)
    return total


def compile_cache_stats() -> Dict[str, Any]:
    """Global pjit executable-cache occupancy, read from jax's two C++
    pjit caches. jaxlib exposes no per-entry byte size, so ``bytes`` is
    reported as ``None`` rather than a fabricated number."""
    from jax._src import pjit as _pjit
    caches = (_pjit._cpp_pjit_cache_fun_only,
              _pjit._cpp_pjit_cache_explicit_attributes)
    return {"available": True,
            "entries": sum(int(c.size()) for c in caches),
            "capacity": sum(int(c.capacity()) for c in caches),
            "bytes": None}


def device_census() -> Dict[str, Any]:
    """Ground-truth device accounting: every live jax array's nbytes
    (what the ledger's per-engine device charges must sum to) plus
    compile-cache occupancy."""
    try:
        import jax
        arrs = jax.live_arrays()
    except Exception:                                  # pragma: no cover
        return {"available": False, "total_bytes": 0, "live_arrays": 0,
                "compile_cache": compile_cache_stats()}
    return {
        "available": True,
        "total_bytes": int(sum(int(a.nbytes) for a in arrs)),
        "live_arrays": len(arrs),
        "compile_cache": compile_cache_stats(),
    }


# --------------------------------------------------------------------------
# provider report shape
# --------------------------------------------------------------------------

def report(host: Optional[Dict[str, int]] = None,
           device: Optional[Dict[str, int]] = None,
           docs: int = 0,
           heaviest: Optional[List[Tuple[Any, int]]] = None,
           ) -> Dict[str, Any]:
    """Canonical provider return shape. ``host``/``device`` map
    category → bytes (categories are free-form: ``interner``,
    ``oplog_tail``, ``dedup``, ``state`` ...); ``docs`` is the
    resident-doc count this owner holds; ``heaviest`` is an optional
    pre-ranked ``[(doc_id, bytes), ...]`` for the top-K census."""
    return {"host": dict(host or {}), "device": dict(device or {}),
            "docs": int(docs), "heaviest": list(heaviest or [])}


# --------------------------------------------------------------------------
# idle-age tracking
# --------------------------------------------------------------------------

class IdleAgeTracker:
    """Monotonic last-touch clock per doc row.

    ``touch(rows)`` is ONE numpy scatter (``last[rows] = now``) — the
    drain passes call it once per window with the unique-row vector
    they already compute for the hot-doc sketch, so idle tracking adds
    no per-op cost. Rows never touched are not resident (stamp < 0).

    The tracker grows on demand (``touch`` ensures capacity), so the
    doors do not need to know engine capacity up front."""

    def __init__(self, capacity: int = 0,
                 clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._last = np.full(max(0, int(capacity)), -1.0, dtype=np.float64)
        self.touches = 0          # windows observed, not ops

    def ensure(self, n: int) -> None:
        if n > self._last.shape[0]:
            grown = np.full(max(n, 2 * self._last.shape[0] or 64), -1.0,
                            dtype=np.float64)
            grown[:self._last.shape[0]] = self._last
            self._last = grown

    def touch(self, rows: np.ndarray,
              now: Optional[float] = None) -> None:
        """Stamp ``rows`` (array-like of row indices) as touched now.
        One vectorized scatter; safe under the GIL without a lock."""
        rows = np.asarray(rows, dtype=np.int64)
        if rows.size == 0:
            return
        self.ensure(int(rows.max()) + 1)
        self._last[rows] = self._clock() if now is None else now
        self.touches += 1

    def last_touch(self, row: int) -> Optional[float]:
        """Monotonic stamp of the row's last touch (None = never)."""
        if 0 <= row < self._last.shape[0] and self._last[row] >= 0.0:
            return float(self._last[row])
        return None

    def resident_rows(self) -> np.ndarray:
        return np.nonzero(self._last >= 0.0)[0]

    def ages(self, now: Optional[float] = None) -> np.ndarray:
        """Idle age in seconds of every touched row (float64 vector)."""
        now = self._clock() if now is None else now
        touched = self._last[self._last >= 0.0]
        return now - touched

    def coldest(self, k: int = 8,
                now: Optional[float] = None) -> List[Dict[str, float]]:
        """Top-``k`` longest-idle rows with the exact stamp of their
        last touch — "untouched since tick T", provably."""
        now = self._clock() if now is None else now
        rows = self.resident_rows()
        if rows.size == 0:
            return []
        stamps = self._last[rows]
        order = np.argsort(stamps, kind="stable")[:max(0, int(k))]
        return [{"row": int(rows[i]), "last_touch": float(stamps[i]),
                 "idle_s": float(now - stamps[i])} for i in order]

    def snapshot(self, now: Optional[float] = None) -> Dict[str, Any]:
        ages = self.ages(now)
        out: Dict[str, Any] = {"resident_rows": int(ages.size),
                               "touch_windows": int(self.touches)}
        if ages.size:
            out.update(
                idle_p50_s=float(np.percentile(ages, 50)),
                idle_p99_s=float(np.percentile(ages, 99)),
                idle_max_s=float(ages.max()))
        return out


def idle_age_histogram(ages_s: np.ndarray) -> _telemetry.Histogram:
    """A point-in-time ``Histogram`` of idle ages (seconds), filled
    with one vectorized pass — the ``doc_idle_age_s`` metric family is
    a distribution snapshot, rebuilt at each census (idle age is a
    level, not an accumulating stream; re-observing resident rows into
    a cumulative histogram every tick would inflate it)."""
    h = _telemetry.Histogram()
    ages = np.asarray(ages_s, dtype=np.float64)
    h.n = int(ages.size)
    h.sum_ms = float(ages.sum()) if ages.size else 0.0
    if ages.size:
        idx = np.searchsorted(np.asarray(h.bounds), ages, side="left")
        counts = np.bincount(idx, minlength=len(h.counts))
        h.counts = [int(c) for c in counts]
    return h


# --------------------------------------------------------------------------
# the ledger
# --------------------------------------------------------------------------

class CapacityLedger:
    """Process-wide capacity accounting: pull providers + idle
    trackers, rolled up into one census.

    Providers register with :meth:`register` (weakly — bound methods
    go through ``weakref.WeakMethod``; a collected owner silently
    drops out of the census, mirroring ``MetricsRegistry.attach``).
    """

    def __init__(self):
        self._providers: Dict[str, Any] = {}     # key -> weak callable
        self._idle: Dict[str, Any] = {}          # key -> weak tracker ref
        self._idle_resolvers: Dict[str, Any] = {}
        self._lock = threading.Lock()
        self.budget_bytes: Optional[int] = None
        # cached device walk: a 1 Hz ops ticker must not pay a full
        # live-array walk per beat (scrape-overhead bound, PR 13)
        self._device_cache: Optional[Dict[str, Any]] = None
        self._device_cache_t = 0.0

    # ---------------------------------------------------------- providers

    @staticmethod
    def _weak(fn: Callable[..., Any]) -> Callable[[], Optional[Any]]:
        """A resolver returning the live callable or None. Bound
        methods must not be kept alive through their __self__."""
        if hasattr(fn, "__self__") and fn.__self__ is not None:
            wm = weakref.WeakMethod(fn)
            return lambda: wm()
        return lambda: fn

    def register(self, owner: str,
                 provider: Callable[[], Dict[str, Any]]) -> str:
        """Register a pull provider under ``owner`` (auto-suffixed on
        collision with a still-live registration). Returns the key."""
        with self._lock:
            base, i, key = owner, 1, owner
            while key in self._providers \
                    and self._providers[key]() is not None:
                i += 1
                key = f"{base}{i}"
            self._providers[key] = self._weak(provider)
            return key

    def unregister(self, key: str) -> None:
        with self._lock:
            self._providers.pop(key, None)

    def add_idle_tracker(self, owner: str, tracker: IdleAgeTracker,
                         row_doc_id: Optional[Callable[[int], Any]] = None
                         ) -> str:
        """Attach an idle tracker (weakly). ``row_doc_id`` optionally
        resolves row index → doc id for the coldest-doc census."""
        with self._lock:
            base, i, key = owner, 1, owner
            while key in self._idle and self._idle[key]() is not None:
                i += 1
                key = f"{base}{i}"
            self._idle[key] = weakref.ref(tracker)
            if row_doc_id is not None:
                self._idle_resolvers[key] = self._weak(row_doc_id)
            return key

    def set_budget(self, nbytes: Optional[int]) -> None:
        """Set (or clear) the process doc-memory budget the
        ``memory_budget_headroom`` SLO judges against."""
        self.budget_bytes = None if nbytes is None else int(nbytes)

    # -------------------------------------------------------------- census

    def _live_providers(self) -> List[Tuple[str, Callable]]:
        out = []
        with self._lock:
            for key in list(self._providers):
                fn = self._providers[key]()
                if fn is None:
                    del self._providers[key]
                else:
                    out.append((key, fn))
        return out

    def _live_idle(self) -> List[Tuple[str, IdleAgeTracker,
                                       Optional[Callable]]]:
        out = []
        with self._lock:
            for key in list(self._idle):
                tr = self._idle[key]()
                if tr is None:
                    del self._idle[key]
                    self._idle_resolvers.pop(key, None)
                else:
                    res = self._idle_resolvers.get(key)
                    out.append((key, tr, res() if res else None))
        return out

    def device_census_cached(self, ttl_s: float = 5.0) -> Dict[str, Any]:
        now = time.monotonic()
        if self._device_cache is None \
                or now - self._device_cache_t > ttl_s:
            self._device_cache = device_census()
            self._device_cache_t = now
        return self._device_cache

    def census(self, top_k: int = 8, device: bool = True,
               device_ttl_s: float = 0.0) -> Dict[str, Any]:
        """One full capacity census.

        Host/device/doc totals by owner and category from every live
        provider, the ground-truth device walk (``device_ttl_s > 0``
        serves it from the tick cache), idle-age summaries per
        tracker, and the top-K heaviest / coldest docs."""
        t0 = time.perf_counter()
        host_by_owner: Dict[str, int] = {}
        dev_by_owner: Dict[str, int] = {}
        host_by_cat: Dict[str, int] = {}
        docs_by_owner: Dict[str, int] = {}
        heaviest: List[Dict[str, Any]] = []
        errors: Dict[str, str] = {}
        for key, fn in self._live_providers():
            try:
                rep = fn()
            except Exception as e:   # census must never take a plane down
                errors[key] = repr(e)
                continue
            h = sum(int(v) for v in rep.get("host", {}).values())
            d = sum(int(v) for v in rep.get("device", {}).values())
            host_by_owner[key] = h
            dev_by_owner[key] = d
            docs_by_owner[key] = int(rep.get("docs", 0))
            for cat, v in rep.get("host", {}).items():
                host_by_cat[cat] = host_by_cat.get(cat, 0) + int(v)
            for doc, b in rep.get("heaviest", []):
                heaviest.append({"owner": key, "doc": doc,
                                 "bytes": int(b)})
        heaviest.sort(key=lambda r: r["bytes"], reverse=True)
        host_total = sum(host_by_owner.values())
        dev_total = sum(dev_by_owner.values())

        idle: Dict[str, Any] = {}
        coldest: List[Dict[str, Any]] = []
        for key, tr, resolve in self._live_idle():
            idle[key] = tr.snapshot()
            for row in tr.coldest(top_k):
                row = dict(row, owner=key)
                if resolve is not None:
                    try:
                        row["doc"] = resolve(row["row"])
                    except Exception:
                        pass
                coldest.append(row)
        coldest.sort(key=lambda r: r["idle_s"], reverse=True)

        out: Dict[str, Any] = {
            "host": {"total_bytes": int(host_total),
                     "by_owner": host_by_owner,
                     "by_category": host_by_cat},
            "device": {"total_bytes": int(dev_total),
                       "by_owner": dev_by_owner},
            "docs": {"resident": sum(docs_by_owner.values()),
                     "by_owner": docs_by_owner},
            "idle": idle,
            "top": {"heaviest": heaviest[:max(0, int(top_k))],
                    "coldest": coldest[:max(0, int(top_k))]},
            "budget_bytes": self.budget_bytes,
            "headroom": self.headroom(host_total + dev_total),
        }
        if device:
            out["device"]["walk"] = (
                self.device_census_cached(device_ttl_s) if device_ttl_s
                else device_census())
        if errors:
            out["errors"] = errors
        out["census_ms"] = (time.perf_counter() - t0) * 1e3
        return out

    def headroom(self, used_bytes: Optional[int] = None) -> float:
        """Fraction of the budget still free, clamped to [0, 1]; 1.0
        when no budget is set (headroom without a budget never pages)."""
        if not self.budget_bytes:
            return 1.0
        if used_bytes is None:
            c = self.census(top_k=0, device=False)
            used_bytes = c["host"]["total_bytes"] \
                + c["device"]["total_bytes"]
        free = 1.0 - float(used_bytes) / float(self.budget_bytes)
        return min(1.0, max(0.0, free))

    # -------------------------------------------------------------- gauges

    def publish_gauges(self,
                       registry: Optional[Any] = None,
                       device_ttl_s: float = 5.0) -> Dict[str, Any]:
        """Publish the metric families onto ``registry`` (default: the
        process REGISTRY): ``doc_resident_bytes`` (host charges),
        ``device_buffer_bytes`` (ledger device charges),
        ``device_live_array_bytes`` / ``compile_cache_entries`` (the
        ground-truth walk, tick-cached), ``resident_docs_total``,
        ``doc_memory_budget_bytes`` + ``memory_budget_headroom``, and
        the ``doc_idle_age_s`` distribution snapshot. Returns the
        census it published from."""
        reg = registry if registry is not None else _telemetry.REGISTRY
        c = self.census(top_k=0, device=True, device_ttl_s=device_ttl_s)
        reg.set_gauge("doc_resident_bytes", float(c["host"]["total_bytes"]))
        reg.set_gauge("device_buffer_bytes",
                      float(c["device"]["total_bytes"]))
        walk = c["device"].get("walk") or {}
        if walk.get("available"):
            reg.set_gauge("device_live_array_bytes",
                          float(walk["total_bytes"]))
            reg.set_gauge("compile_cache_entries",
                          float(walk["compile_cache"]["entries"]))
        reg.set_gauge("resident_docs_total", float(c["docs"]["resident"]))
        if self.budget_bytes:
            reg.set_gauge("doc_memory_budget_bytes",
                          float(self.budget_bytes))
        reg.set_gauge("memory_budget_headroom", float(c["headroom"]))
        ages: List[np.ndarray] = []
        for _key, tr, _res in self._live_idle():
            a = tr.ages()
            if a.size:
                ages.append(a)
        if ages:
            reg.histograms["doc_idle_age_s"] = idle_age_histogram(
                np.concatenate(ages))
        return c


#: the process-wide ledger (engines, oplogs, doors all register here)
LEDGER = CapacityLedger()


def _census_for_dump() -> Dict[str, Any]:
    """Compact census for flight-dump headers (no device walk cache —
    dumps are rare and want fresh truth; numpy scalars coerced by the
    dump's _jsonable)."""
    return LEDGER.census(top_k=4, device=True)


_flight.add_dump_context("capacity_census", _census_for_dump)
_flight.add_dump_context("metrics_snapshot",
                         lambda: _telemetry.REGISTRY.snapshot())
