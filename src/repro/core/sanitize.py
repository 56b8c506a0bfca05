"""Runtime sanitizer: accounting-checking proxies for store and merge.

Activated by ``REPRO_SANITIZE=1`` in the environment or
``SuperblockConfig.sanitize``; off by default and free when off.  Three
checks, mirroring the invariants salint enforces statically
(``docs/static_analysis.md``):

* **accounting cross-check** — on every fetch the backend's claimed
  ``resident_bytes`` is recomputed from the actual live cache allocations
  and the LRU budget invariant (``resident <= cache_budget_bytes``) is
  asserted (the paper's bounded-residency claim, checked at every instant
  it could break);
* **halo-window byte-exactness** — a sampled subset of every gather's
  windows is re-read through the *uncached* item path (``read_items``
  preads straight from disk) and compared byte-exact, so a stale or
  mis-haloed cached chunk cannot silently serve wrong windows;
* **merge-order verification** — every tile the merge emits is checked
  sorted w.r.t. :func:`repro.core.store.lex_less_rows` on sampled adjacent
  pairs (and across tile seams), served by a private audit store so the
  build's own traffic accounting stays untouched.

Violations raise :class:`SanitizeError` (an ``AssertionError`` subclass:
sanitized runs treat invariant breaks as hard failures).
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

from repro.config import SAConfig
from repro.core.store import CorpusStore, StoreBackend, lex_less_rows


class SanitizeError(AssertionError):
    """A runtime invariant check failed under REPRO_SANITIZE."""


def sanitize_enabled(sb=None) -> bool:
    """True when the sanitizer is on: ``REPRO_SANITIZE`` set to anything but
    ``0``/empty, or ``sb.sanitize`` on the given config."""
    if os.environ.get("REPRO_SANITIZE", "") not in ("", "0"):
        return True
    return bool(sb is not None and getattr(sb, "sanitize", False))


def unwrap_backend(backend: StoreBackend) -> StoreBackend:
    """The real backend behind any proxy layers — sanitizing, retrying,
    throttling, fault-injecting — all of which follow the wrapper idiom of
    holding the wrapped backend as ``.inner`` (for ``isinstance`` dispatch
    on the backend's residency regime)."""
    depth = 0
    while "inner" in getattr(backend, "__dict__", ()) and depth < 32:
        backend = backend.inner
        depth += 1
    return backend


def _sample_indices(m: int, sample: int) -> np.ndarray:
    """Up to ``sample`` indices spread evenly over ``range(m)`` —
    deterministic, endpoints included (chunk edges are where halo bugs
    live)."""
    if m <= 0:
        return np.zeros(0, np.int64)
    return np.unique(np.linspace(0, m - 1, num=min(m, sample)).astype(np.int64))


class SanitizingBackend(StoreBackend):
    """Accounting-checking proxy around any :class:`StoreBackend`.

    Transparent to callers (geometry and counters delegate to the wrapped
    backend); every ``gather`` additionally (1) recomputes the live cache
    bytes from the cache dict itself and cross-checks the backend's
    ``resident_bytes`` claim and the LRU budget bound, and (2) re-reads a
    sampled subset of the returned windows through the uncached
    ``read_items`` path and requires byte-exact agreement.
    """

    def __init__(self, inner: StoreBackend, sample: int = 4):
        self.inner = inner
        self.sample = max(1, int(sample))
        self.checks = 0
        self.oracle_windows_checked = 0
        self.observed_peak_bytes = 0

    def __getattr__(self, name: str):
        return getattr(self.inner, name)

    @property
    def resident_bytes(self) -> int:
        return self.inner.resident_bytes

    def close(self) -> None:
        self.inner.close()

    def read_items(self, lo: int, hi: int) -> np.ndarray:
        before = self.inner.resident_bytes
        out = self.inner.read_items(lo, hi)
        if self.inner.resident_bytes != before:
            raise SanitizeError(
                "read_items changed backend residency "
                f"({before} -> {self.inner.resident_bytes} B): staging must "
                "bypass the window cache")
        return out

    def gather(self, gidx: np.ndarray, depth: np.ndarray) -> np.ndarray:
        gidx = np.asarray(gidx, np.int64)
        m = int(gidx.shape[0])
        depth = np.broadcast_to(np.asarray(depth, np.int64), (m,))
        out = self.inner.gather(gidx, depth)
        self.checks += 1
        self._check_cache_accounting()
        self.observed_peak_bytes = max(
            self.observed_peak_bytes, self.inner.resident_bytes)
        sel = _sample_indices(m, self.sample)
        if sel.size:
            oracle = self._oracle_windows(gidx[sel], depth[sel])
            if not np.array_equal(out[sel], oracle):
                bad = int(sel[(out[sel] != oracle).any(axis=1).argmax()])
                raise SanitizeError(
                    f"cached window for gidx={int(gidx[bad])} "
                    f"depth={int(depth[bad])} differs from the uncached "
                    f"oracle read (corrupted or mis-haloed cache chunk)")
            self.oracle_windows_checked += int(sel.size)
        return out

    # -- checks -------------------------------------------------------------
    def _check_cache_accounting(self) -> None:
        inner = self.inner
        cache = getattr(inner, "_cache", None)
        if cache is None:
            return  # backend has no cache to account for
        live = sum(int(c.nbytes) for c in cache.values())
        claimed = inner.resident_bytes
        if live != claimed:
            raise SanitizeError(
                f"backend accounting leak: resident_bytes claims {claimed} B "
                f"but live cache allocations sum to {live} B")
        budget = getattr(inner, "cache_budget_bytes", None)
        if budget is not None and live > budget:
            raise SanitizeError(
                f"LRU budget invariant broken: {live} B resident exceeds "
                f"cache_budget_bytes={budget} B after eviction")

    def _oracle_windows(self, gidx: np.ndarray, depth: np.ndarray) -> np.ndarray:
        """Reference windows via the uncached item path (pread on the
        chunked backend) — the geometry mirror of ``StoreBackend.gather``."""
        inner = self.inner
        k = inner.k
        out = np.zeros((gidx.shape[0], k), np.int32)
        if inner.text_mode:
            pos = np.minimum(gidx + depth * k, inner.n)
            for i, p in enumerate(pos.tolist()):
                w = inner.read_items(int(p), int(p) + k)
                out[i, : w.shape[0]] = w
        else:
            mask = (1 << inner.stride_bits) - 1
            row = (gidx >> inner.stride_bits).astype(np.int64)
            off = np.minimum((gidx & mask) + depth * k, inner.max_len - 1)
            for i in range(gidx.shape[0]):
                r = inner.read_items(int(row[i]), int(row[i]) + 1)
                w = r.reshape(-1)[int(off[i]) : int(off[i]) + k]
                out[i, : w.shape[0]] = w
        return out


class SanitizingSink:
    """Order-verifying proxy around the merge's output sink.

    Checks sampled adjacent pairs of every appended piece — plus the seam
    against the previous piece's last suffix — against the true suffix
    order (:func:`lex_less_rows` over packed key windows, ties by global
    index).  Fetches go through a private audit :class:`CorpusStore` over
    the same backend, so the build's own request/byte counters (asserted
    by the traffic-gate tests) are untouched.
    """

    def __init__(self, sink, backend: StoreBackend, cfg: SAConfig,
                 sample: int = 4, request_capacity: int = 4096):
        self._sink = sink
        self._audit = CorpusStore(None, cfg, backend=backend,
                                  request_capacity=request_capacity)
        self.sample = max(1, int(sample))
        self._prev_last: Optional[int] = None
        self.pairs_checked = 0

    def __getattr__(self, name: str):
        return getattr(self._sink, name)

    def append(self, piece: np.ndarray) -> None:
        p = np.asarray(piece, np.int64).reshape(-1)
        if p.size:
            if self._prev_last is not None:
                self._check_pair(self._prev_last, int(p[0]))
            for i in _sample_indices(p.size - 1, self.sample).tolist():
                self._check_pair(int(p[i]), int(p[i + 1]))
            self._prev_last = int(p[-1])
        self._sink.append(piece)

    def _check_pair(self, a: int, b: int) -> None:
        """Assert ``suffix(a) < suffix(b)`` (ties by index) or raise."""
        self.pairs_checked += 1
        if a == b:
            raise SanitizeError(f"merge emitted duplicate suffix {a}")
        store = self._audit
        for d in range(store.max_window_depth):
            ka, ea = store.fetch_keys(np.array([a], np.int64), d)
            kb, _ = store.fetch_keys(np.array([b], np.int64), d)
            lt, eq = lex_less_rows(kb, ka)
            if lt[0]:
                raise SanitizeError(
                    f"merge emitted out-of-order pair: suffix {b} sorts "
                    f"before its predecessor {a} (diverge at window depth "
                    f"{d})")
            if not eq[0]:
                return  # a < b strictly at this depth
            if ea[0]:
                # equal content and both suffixes ended: index breaks the tie
                if a > b:
                    raise SanitizeError(
                        f"merge emitted equal-content suffixes {a}, {b} in "
                        f"non-index order")
                return
        raise SanitizeError(
            f"suffix comparison of {a}, {b} overran the window depth bound")


def check_footprint(store: CorpusStore,
                    backend: Optional[StoreBackend] = None) -> None:
    """End-of-build cross-check of the store's Footprint accounting against
    independently recomputed backend state."""
    inner = unwrap_backend(backend if backend is not None else store.backend)
    cache = getattr(inner, "_cache", None)
    if cache is not None:
        live = sum(int(c.nbytes) for c in cache.values())
        if live != inner.resident_bytes:
            raise SanitizeError(
                f"backend accounting leak at build end: resident_bytes "
                f"claims {inner.resident_bytes} B, live cache holds {live} B")
        budget = getattr(inner, "cache_budget_bytes", None)
        if budget is not None and live > budget:
            raise SanitizeError(
                f"LRU budget invariant broken at build end: {live} B "
                f"resident exceeds cache_budget_bytes={budget} B")
    if store.frontier_bytes < 0:
        raise SanitizeError(
            f"negative merge frontier ({store.frontier_bytes} B): more "
            f"window bytes released than registered")
    store._note_resident()
    current = inner.resident_bytes + store.frontier_bytes
    if store.peak_resident_bytes < current:
        raise SanitizeError(
            f"peak_resident_bytes ({store.peak_resident_bytes} B) below "
            f"current residency ({current} B): peak tracking missed a fetch")
