"""Sharded suffix-array query engine + the unified build→save→open→query API.

The production *consumer* side of the index every construction PR optimized
(paper §I: the SA exists for pattern matching — alignment seeds, substring
counting, contamination lookup).  Two layers:

:class:`ShardedSAEngine` — batched count/locate/align over an already-built
index (SA + corpus behind any :class:`~repro.core.store.StoreBackend`):

* **Sharding.**  The SA is split into S contiguous ranges at splitter
  suffixes ``sa[bounds[s]]`` — the same first-suffix-of-run splitter notion
  the out-of-core merge partitions by.  One batched compare per splitter
  routes every query of a batch to its target shard (`< P` and `<=' P` are
  downward-closed over suffix order, so prefix-count gives the shard id);
  each shard's binary search then runs over ``log(n/S)`` rounds.  S defaults
  to the local device count: shards are independent, so a deployment maps
  them across devices; here all shards' live queries share each batched
  compare round.
* **Batched search.**  All queries advance one binary-search level per
  round: the engine gathers each live query's mid-suffix window from the
  store and issues **one** device compare for the whole batch
  (``kernels/pattern_cmp`` under ``cfg.use_pallas``, the numpy mirror
  ``core.search.masked_cmp_np`` otherwise).
* **LCP acceleration.**  With the build's LCP array (``emit_lcp``), per-mid
  LLCP/RLCP values over each shard's binary-search tree drive the classic
  Manber–Myers bound: a query re-compares only tokens it has not already
  matched, so total compare work is O(m + log n) per query instead of
  O(m log n).  Without an LCP array the engine still avoids re-comparing
  the min(l, r) known-equal prefix.
* **Hot-pattern LRU.**  A byte-budgeted result cache in front memoizes
  pattern → (lo, hi); count/locate/align all derive from the cached range.

:class:`SuffixArrayIndex` — the facade: ``build(...)`` (any corpus form,
in-core or out-of-core), ``save(dir)`` / ``open(dir)`` (the persistent index
layout of ``repro.core.index_io``), ``count/locate/align(batch)``.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.config import SAConfig, SuperblockConfig, replace as cfg_replace
from repro.core.sanitize import unwrap_backend
from repro.core.search import masked_cmp_np
from repro.core.store import (
    ChunkedFileBackend,
    CorpusStore,
    InMemoryBackend,
    StoreBackend,
)
from repro.core.trace import span

__all__ = ["ShardedSAEngine", "SuffixArrayIndex"]


# ---------------------------------------------------------------------------
# hot-pattern result cache
# ---------------------------------------------------------------------------


class _ResultCache:
    """Byte-budgeted LRU of pattern bytes -> (lo, hi)."""

    _ENTRY_OVERHEAD = 64  # dict slot + the two ints, approximately

    def __init__(self, budget_bytes: int):
        self.budget = int(budget_bytes)
        self._d: "OrderedDict[bytes, Tuple[int, int]]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0

    def _cost(self, key: bytes) -> int:
        return len(key) + self._ENTRY_OVERHEAD

    def get(self, key: bytes) -> Optional[Tuple[int, int]]:
        v = self._d.get(key)
        if v is None:
            self.misses += 1
            return None
        self._d.move_to_end(key)
        self.hits += 1
        return v

    def put(self, key: bytes, val: Tuple[int, int]) -> None:
        if self.budget <= 0 or self._cost(key) > self.budget:
            return
        if key in self._d:
            self._d.move_to_end(key)
            self._d[key] = val
            return
        while self._d and self._bytes + self._cost(key) > self.budget:
            old, _ = self._d.popitem(last=False)
            self._bytes -= self._cost(old)
        self._d[key] = val
        self._bytes += self._cost(key)

    @property
    def resident_bytes(self) -> int:
        return self._bytes


def _as_batch(patterns) -> Tuple[List[np.ndarray], bool]:
    """Normalize to (list of 1-D int64 patterns, was_single_pattern)."""
    if isinstance(patterns, np.ndarray):
        if patterns.ndim == 2:
            return [np.asarray(r, np.int64) for r in patterns], False
        return [np.asarray(patterns, np.int64).ravel()], True
    seq = list(patterns)
    if seq and isinstance(seq[0], (int, np.integer)):
        return [np.asarray(seq, np.int64)], True
    return [np.asarray(p, np.int64).ravel() for p in seq], False


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ShardedSAEngine:
    """Batched queries over (store, sa[, lcp]); see module docstring."""

    def __init__(
        self,
        store: CorpusStore,
        sa: np.ndarray,
        lcp: Optional[np.ndarray] = None,
        num_shards: int = 0,
        cache_budget_bytes: int = 1 << 20,
        use_pallas: Optional[bool] = None,
        block: int = 256,
    ):
        self.store = store
        self.sa = sa
        self.lcp = lcp
        n = int(np.asarray(sa).shape[0])
        if num_shards <= 0:
            import jax

            num_shards = jax.local_device_count()
        self.num_shards = max(1, min(int(num_shards), max(n, 1)))
        s = self.num_shards
        self.bounds = np.array([i * n // s for i in range(s + 1)], np.int64)
        # splitters: the first suffix of every shard but the first — the
        # merge's run-splitter notion reused for query routing
        self.splitters = np.asarray(
            sa[self.bounds[1:-1]], np.int64) if s > 1 else np.zeros(0, np.int64)
        self.use_pallas = (store.cfg.use_pallas if use_pallas is None
                          else bool(use_pallas))
        self.block = int(block)
        self.cache = _ResultCache(cache_budget_bytes)
        self._llcp: Optional[np.ndarray] = None
        self._rlcp: Optional[np.ndarray] = None
        self.stats: Dict[str, Any] = {
            "queries": 0, "search_rounds": 0, "compare_rounds": 0,
            "compare_rows": 0, "t_compare_s": 0.0,
        }
        if lcp is not None and n:
            self._build_llcp()

    # -- LLCP/RLCP precompute ------------------------------------------------
    def _build_llcp(self) -> None:
        """Per-shard LLCP/RLCP over the canonical binary-search tree.

        Each position in a shard's open interval ``(L-1, R)`` is the mid of
        exactly one tree node, so one global array pair serves every shard;
        sentinel endpoints (lo = L-1, hi = R) share no prefix with anything
        (their lcp contribution is 0).  O(n) adjacent-lcp mins, O(log) deep.
        """
        n = int(np.asarray(self.sa).shape[0])
        lcpadj = np.asarray(self.lcp, np.int64)
        llcp = np.zeros(n, np.int64)
        rlcp = np.zeros(n, np.int64)

        def fill(lo: int, hi: int, left: int, right: int) -> int:
            if hi - lo == 1:
                return 0 if (lo < left or hi >= right) else int(lcpadj[hi])
            mid = (lo + hi) // 2
            a = fill(lo, mid, left, right)
            b = fill(mid, hi, left, right)
            llcp[mid], rlcp[mid] = a, b
            return 0 if (lo < left or hi >= right) else min(a, b)

        for s in range(self.num_shards):
            left, right = int(self.bounds[s]), int(self.bounds[s + 1])
            if right - left >= 1:
                fill(left - 1, right, left, right)
        self._llcp, self._rlcp = llcp, rlcp

    # -- batched compares ----------------------------------------------------
    def _cmp_rows(self, win: np.ndarray, pw: np.ndarray, start: np.ndarray,
                  stop: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """One device (or numpy) masked compare over all live rows: the
        host-to-device transfer, the kernel and its result back on the
        host."""
        self.stats["compare_rounds"] += 1
        self.stats["compare_rows"] += int(win.shape[0])
        with span("serve.compare", self.stats, "t_compare_s"):
            if self.use_pallas:
                from repro.kernels import ops as kops

                out = kops.pattern_cmp(
                    win.astype(np.int32), pw.astype(np.int32),
                    start.astype(np.int32), stop.astype(np.int32),
                    block=self.block,
                )
                return out[:, 0], out[:, 1].astype(np.int64)
            return masked_cmp_np(win, pw, start, stop)

    def _compare_batch(
        self,
        gidx: np.ndarray,
        pat_rows: np.ndarray,
        pat_len: np.ndarray,
        t0: np.ndarray,
        pi: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Trichotomy of suffix(gidx[i]) vs pattern ``pi[i]``, starting from
        ``t0[i]`` already-matched tokens.

        Returns ``(cmp, t)``: cmp in {-1, 0, +1} with 0 = the pattern is a
        prefix of the suffix, and t = matched tokens (capped at the pattern
        length).  Progressive: one store fetch + one batched compare per
        window level still in play; a suffix ending mid-pattern compares its
        padding 0 against a real token and resolves ``-1`` with no special
        case.
        """
        gidx = np.asarray(gidx, np.int64).ravel()
        q = gidx.shape[0]
        if pi is None:
            pi = np.arange(q)
        plen = pat_len[pi]
        k = self.store.k
        cmp = np.zeros(q, np.int32)
        t = np.asarray(t0, np.int64).copy()
        undecided = t < plen  # t0 == plen: fully matched already
        # every round resolves each live query's current window level
        for _ in range(self.store.max_window_depth + 1):
            if not undecided.any():
                return cmp, t
            idx = np.flatnonzero(undecided)
            lv = t[idx] // k
            win = self.store.fetch_windows(gidx[idx], lv)
            start = t[idx] - lv * k
            stop = np.minimum(k, plen[idx] - lv * k)
            cols = lv[:, None] * k + np.arange(k, dtype=np.int64)[None, :]
            valid = cols < plen[idx][:, None]
            cc = np.minimum(cols, pat_rows.shape[1] - 1)
            pw = np.where(valid, pat_rows[pi[idx][:, None], cc], 0)
            c, m_in = self._cmp_rows(win, pw, start, stop)
            t[idx] += m_in
            cmp[idx] = c
            done = (c != 0) | (t[idx] >= plen[idx])
            undecided[idx[done]] = False
        raise RuntimeError("batched compare overran the window bound")

    def _route(self, pat_rows: np.ndarray, pat_len: np.ndarray,
               upper: bool) -> np.ndarray:
        """Target shard per query: one batched trichotomy against all
        splitters; prefix-count of splitters below the query's bound class
        (both classes are downward-closed over suffix order)."""
        s, q = self.num_shards, pat_len.shape[0]
        if s == 1:
            return np.zeros(q, np.int64)
        g = np.tile(self.splitters, q)
        pi = np.repeat(np.arange(q), s - 1)
        with span("serve.round"):
            c, _ = self._compare_batch(
                g, pat_rows, pat_len, np.zeros(g.shape[0], np.int64), pi=pi)
        c = c.reshape(q, s - 1)
        below = (c <= 0) if upper else (c < 0)  # prefix-match counts as <='
        return below.sum(axis=1).astype(np.int64)

    def _bound_batch(self, pat_rows: np.ndarray, pat_len: np.ndarray,
                     upper: bool) -> np.ndarray:
        """Vectorized Manber–Myers bound for every query at once.

        Open-endpoint invariant per query: ``(lo, hi)`` with sentinels
        ``lo = L-1`` (-inf) and ``hi = R`` (+inf), ``l = lcp(P, sa[lo])``,
        ``r = lcp(P, sa[hi])``.  Rounds are shared across all queries and
        shards (disjoint per-shard search trees index one global LLCP/RLCP
        pair); per round, LLCP/RLCP decide what they can and the remainder
        issues one batched explicit compare starting at its proven offset.
        """
        shard = self._route(pat_rows, pat_len, upper)
        lo = self.bounds[shard] - 1
        hi = self.bounds[shard + 1].copy()
        q = pat_len.shape[0]
        l = np.zeros(q, np.int64)
        r = np.zeros(q, np.int64)
        use_lr = self._llcp is not None
        while True:
            act = np.flatnonzero(hi - lo > 1)
            if act.size == 0:
                return hi
            with span("serve.round"):
                self.stats["search_rounds"] += 1
                mid = (lo[act] + hi[act]) >> 1
                la, ra = l[act], r[act]
                right = np.zeros(act.size, bool)
                newl, newr = la.copy(), ra.copy()
                if use_lr:
                    ne = la != ra
                    x = np.where(la > ra, self._llcp[mid], self._rlcp[mid])
                    mx = np.maximum(la, ra)
                    gt, ltm = ne & (x > mx), ne & (x < mx)
                    c1, c2 = la > ra, ra > la
                    # x beyond the deeper endpoint's agreement: mid sides with
                    # that endpoint (l/r carry over); x short of it: mid sides
                    # against it and its own lcp is exactly x.
                    right |= c1 & gt
                    newr = np.where(c1 & ltm, x, newr)
                    right |= c2 & ltm
                    newl = np.where(c2 & ltm, x, newl)
                    need = ~(gt | ltm)
                    t0 = np.where(ne, mx, la)  # proven-equal prefix at the mid
                else:
                    need = np.ones(act.size, bool)
                    t0 = np.minimum(la, ra)
                ni = np.flatnonzero(need)
                if ni.size:
                    ai = act[ni]
                    c, t = self._compare_batch(
                        np.asarray(self.sa[mid[ni]], np.int64),
                        pat_rows, pat_len, t0[ni], pi=ai)
                    re = (c < 0) | (c == 0) if upper else (c < 0)
                    right[ni] = re
                    newl[ni] = np.where(re, t, newl[ni])
                    newr[ni] = np.where(re, newr[ni], t)
                lo[act] = np.where(right, mid, lo[act])
                hi[act] = np.where(right, hi[act], mid)
                l[act] = np.where(right, newl, l[act])
                r[act] = np.where(right, r[act], newr)

    # -- public batched queries ---------------------------------------------
    def ranges(self, patterns: Sequence) -> np.ndarray:
        """(q, 2) int64 ``[lo, hi)`` SA ranges, cache-served when hot."""
        pats = [np.asarray(p, np.int64).ravel() for p in patterns]
        q = len(pats)
        out = np.zeros((q, 2), np.int64)
        self.stats["queries"] += q
        keys = [p.tobytes() for p in pats]
        miss: "OrderedDict[bytes, List[int]]" = OrderedDict()
        for i, key in enumerate(keys):
            hit = self.cache.get(key)
            if hit is None:
                miss.setdefault(key, []).append(i)
            else:
                out[i] = hit
        if miss:
            res = self._search([pats[g[0]] for g in miss.values()])
            for (key, g), row in zip(miss.items(), res, strict=True):
                out[g] = row
                self.cache.put(key, (int(row[0]), int(row[1])))
        return out

    def _search(self, pats: List[np.ndarray]) -> np.ndarray:
        n = int(np.asarray(self.sa).shape[0])
        u = len(pats)
        out = np.zeros((u, 2), np.int64)
        # tokens < 1 collide with the end-of-suffix padding: such patterns
        # can never occur in a corpus of real (>= 1) tokens
        live = [i for i, p in enumerate(pats) if p.size == 0 or p.min() >= 1]
        if not live:
            return out
        lmax = max(1, max(pats[i].size for i in live))
        rows = np.zeros((len(live), lmax), np.int64)
        plen = np.zeros(len(live), np.int64)
        for j, i in enumerate(live):
            rows[j, : pats[i].size] = pats[i]
            plen[j] = pats[i].size
        if n == 0:
            return out
        lo = self._bound_batch(rows, plen, upper=False)
        hi = self._bound_batch(rows, plen, upper=True)
        out[live, 0] = lo
        out[live, 1] = hi
        return out

    def count(self, patterns: Sequence) -> np.ndarray:
        rg = self.ranges(patterns)
        return rg[:, 1] - rg[:, 0]

    def locate(self, patterns: Sequence) -> List[np.ndarray]:
        """Per pattern: ascending global indexes of every occurrence
        (text positions, or packed ``row << stride | off`` for reads)."""
        return [
            np.sort(np.asarray(self.sa[lo:hi], np.int64))
            for lo, hi in self.ranges(patterns)
        ]

    def align(self, patterns: Sequence) -> List[List[Tuple[int, int]]]:
        """Per pattern: sorted (read_id, offset) pairs (reads mode only)."""
        if self.store.text_mode:
            raise ValueError("align() needs a reads-mode index; "
                             "use locate() for text corpora")
        sb = self.store.stride_bits
        mask = (1 << sb) - 1
        return [
            [(int(g >> sb), int(g & mask)) for g in occ]
            for occ in self.locate(patterns)
        ]

    def engine_stats(self) -> Dict[str, Any]:
        """Running totals since the engine was made.

        ``cache_hits`` / ``cache_misses`` / ``cache_resident_bytes`` are the
        hot-pattern *result* cache's (a query answered without a search).
        ``chunk_hits`` / ``chunk_misses`` / ``chunk_evictions`` are the
        corpus *chunk* cache's (``ChunkedFileBackend``'s LRU, one count per
        chunk a window fetch touches), ``chunk_reordered_gathers`` the
        gathers that served resident chunks ahead of a missing one with a
        lower id; 0 on a backend without chunks.
        ``compare_rows`` counts the live rows sent to the compare over all
        rounds.  Seconds: ``t_compare_s`` in the compare (transfer, kernel,
        result back), ``t_fetch_s`` in the store's window fetches,
        ``t_chunk_read_s`` in chunk-cache misses (eviction, read, crc).
        """
        backend = unwrap_backend(self.store.backend)
        chunked = isinstance(backend, ChunkedFileBackend)
        return {
            **self.stats,
            "num_shards": self.num_shards,
            "lcp_accelerated": self._llcp is not None,
            "cache_hits": self.cache.hits,
            "cache_misses": self.cache.misses,
            "cache_resident_bytes": self.cache.resident_bytes,
            "chunk_hits": backend.cache_hits if chunked else 0,
            "chunk_misses": backend.cache_misses if chunked else 0,
            "chunk_evictions": backend.evictions if chunked else 0,
            "chunk_reordered_gathers": (backend.reordered_gathers if chunked
                                        else 0),
            "store_requests": self.store.requests,
            "store_response_bytes": self.store.response_bytes,
            "t_fetch_s": self.store.timers["t_fetch_s"],
            "t_chunk_read_s": (backend.timers["t_chunk_read_s"] if chunked
                               else 0.0),
        }


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------


class SuffixArrayIndex:
    """One object for the index's whole life: build → save → open → query.

    Examples::

        idx = SuffixArrayIndex.build(reads, cfg=SAConfig(vocab_size=4))
        idx.count(pattern)                  # one pattern -> int
        idx.align([p1, p2, p3])             # batch -> list of match lists
        idx.save("/data/my_index")

        idx = SuffixArrayIndex.open("/data/my_index")   # no rebuild
        idx.locate(pattern)

    Queries accept one pattern (a 1-D sequence of ints) or a batch (list of
    sequences / 2-D array) and return unbatched / batched results
    correspondingly.  ``build(index_dir=...)`` persists during construction
    (out-of-core builds stream the SA/LCP straight to that directory).
    """

    def __init__(
        self,
        store: CorpusStore,
        sa: np.ndarray,
        lcp: Optional[np.ndarray] = None,
        index_dir: Optional[str] = None,
        stats: Optional[Dict[str, Any]] = None,
        num_shards: int = 0,
        result_cache_bytes: int = 1 << 20,
        use_pallas: Optional[bool] = None,
    ):
        self.store = store
        self.cfg = store.cfg
        self.sa = sa
        self.lcp = lcp
        self.index_dir = index_dir
        self.build_stats = stats or {}
        self._engine_kw = dict(
            num_shards=num_shards, cache_budget_bytes=result_cache_bytes,
            use_pallas=use_pallas,
        )
        self._engine: Optional[ShardedSAEngine] = None

    # -- lifecycle -----------------------------------------------------------
    @classmethod
    def build(
        cls,
        corpus,
        lengths=None,
        cfg: Optional[SAConfig] = None,
        sb: Optional[SuperblockConfig] = None,
        index_dir: Optional[str] = None,
        mesh=None,
        emit_lcp: bool = True,
        **engine_kw,
    ) -> "SuffixArrayIndex":
        """Construct (via ``build_suffix_array_auto``: single-pass or
        out-of-core as the plan decides) and wrap for querying.

        ``index_dir`` persists the index during the build (it doubles as
        the superblock ``spill_dir``, so streamed output lands there
        directly); the returned object serves from that directory.
        ``emit_lcp`` (default on) keeps the O(m + log n) bound.
        """
        from repro.core.superblock import build_suffix_array_auto

        cfg = cfg or SAConfig()
        sb = sb or SuperblockConfig()
        if index_dir is not None:
            sb = cfg_replace(sb, spill_dir=index_dir, write_manifest=True,
                             emit_lcp=emit_lcp or sb.emit_lcp)
        elif emit_lcp and not sb.emit_lcp:
            sb = cfg_replace(sb, emit_lcp=True)
        res = build_suffix_array_auto(
            corpus, lengths=lengths, cfg=cfg, sb=sb, mesh=mesh)
        if index_dir is not None:
            idx = cls.open(
                index_dir,
                store_backend=("memory" if sb.store_backend == "memory"
                               else "chunked"),
                cache_budget_bytes=sb.cache_budget_bytes,
                **engine_kw,
            )
            res.stats["t_open_s"] = idx.build_stats["t_open_s"]
            idx.build_stats = res.stats
            return idx
        backend = _serving_backend(corpus, cfg, sb)
        store = CorpusStore(None, cfg, backend=backend,
                            request_capacity=sb.request_capacity)
        return cls(store, res.suffix_array, lcp=res.lcp, stats=res.stats,
                   **engine_kw)

    @classmethod
    def open(
        cls,
        index_dir: str,
        store_backend: str = "chunked",
        cache_budget_bytes: int = 0,
        request_capacity: int = 4096,
        verify: str = "lazy",
        **engine_kw,
    ) -> "SuffixArrayIndex":
        """Serve a previously built index directory — no rebuild.

        ``store_backend="chunked"`` (default) keeps the corpus on disk
        behind the budgeted LRU chunk cache; ``"memory"`` materializes it.
        ``verify`` sets the integrity posture (``"eager"`` / ``"lazy"`` /
        ``"off"`` — see :func:`repro.core.index_io.open_index`); failures
        raise :class:`repro.core.integrity.CorruptionError` naming the
        artifact.  ``build_stats`` are the manifest's, with ``t_open_s``:
        the seconds this open took.
        """
        from repro.core import index_io

        opened: Dict[str, float] = {}
        with span("index.open", opened, "t_open_s"):
            backend, sa, lcp, manifest = index_io.open_index(
                index_dir, store_backend=store_backend,
                cache_budget_bytes=cache_budget_bytes, verify=verify,
            )
            store = CorpusStore(None, SAConfig(**manifest["sa_config"]),
                                backend=backend,
                                request_capacity=request_capacity)
        return cls(store, sa, lcp=lcp, index_dir=index_dir,
                   stats={**(manifest.get("stats") or {}), **opened},
                   **engine_kw)

    def save(self, index_dir: str) -> str:
        """Write the persistent layout; returns the manifest path.  The
        corpus is serialized into the directory unless this index already
        serves from a persistent chunked file (then the manifest points at
        it)."""
        from repro.core import index_io

        corpus_ref = getattr(self.store.backend, "path", None)
        if corpus_ref is not None:
            corpus_ref = os.path.abspath(corpus_ref)
        mpath = index_io.save_index(
            index_dir, self.cfg, self.store.backend, self.sa, self.lcp,
            stats=self.build_stats, corpus_ref=corpus_ref,
        )
        self.index_dir = index_dir
        return mpath

    def close(self) -> None:
        self.store.backend.close()

    def __enter__(self) -> "SuffixArrayIndex":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- queries -------------------------------------------------------------
    @property
    def engine(self) -> ShardedSAEngine:
        if self._engine is None:
            self._engine = ShardedSAEngine(
                self.store, self.sa, lcp=self.lcp, **self._engine_kw)
        return self._engine

    def count(self, patterns):
        """Occurrences per pattern: int for one pattern, (q,) for a batch."""
        pats, single = _as_batch(patterns)
        c = self.engine.count(pats)
        return int(c[0]) if single else c

    def locate(self, patterns):
        """Sorted occurrence positions (global indexes) per pattern."""
        pats, single = _as_batch(patterns)
        occ = self.engine.locate(pats)
        return occ[0] if single else occ

    def align(self, patterns):
        """Sorted (read_id, offset) matches per pattern (reads mode)."""
        pats, single = _as_batch(patterns)
        hits = self.engine.align(pats)
        return hits[0] if single else hits

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "backend": type(self.store.backend).__name__,
            "suffixes": int(np.asarray(self.sa).shape[0]),
            "has_lcp": self.lcp is not None,
            "index_dir": self.index_dir,
        }
        if self._engine is not None:
            out.update(self._engine.engine_stats())
        return out


def _serving_backend(corpus, cfg: SAConfig,
                     sb: SuperblockConfig) -> StoreBackend:
    """Backend for querying a freshly built, non-persisted index."""
    from repro.core.sanitize import SanitizingBackend, sanitize_enabled

    if isinstance(corpus, StoreBackend):
        backend = corpus
    elif isinstance(corpus, (str, os.PathLike)):
        backend = ChunkedFileBackend(
            os.fspath(corpus), cfg,
            cache_budget_bytes=max(sb.cache_budget_bytes, 0))
    else:
        backend = InMemoryBackend(np.asarray(corpus, np.int32), cfg)
    if sanitize_enabled(sb) and not isinstance(backend, SanitizingBackend):
        backend = SanitizingBackend(backend)
    return backend
