"""Out-of-core suffix-array construction via superblocks.

The paper's headline result is *scale*: 6.7 TB of suffixes on a 16-node
cluster, with only indexes in flight while the raw data stays resident in the
in-memory store (§IV-V).  The single-pass pipeline (``core/pipeline.py``)
requires every 16-byte suffix record of the corpus to fit one ``shard_map``
run; this module removes that ceiling by the standard block-wise route of the
distributed-SA literature (Haag/Kurpicz/Sanders/Schimek '24, Bingmann/Gog/
Kurpicz '16): partition, solve blocks with the existing machinery, merge.

Phases (see :func:`build_suffix_array_superblock`):

1. **Partition** — the corpus is split into S contiguous superblocks such
   that each block's record set fits one run (:func:`plan_superblocks`).
2. **Local SAs** — every superblock runs the ordinary distributed pipeline.
   Reads mode: block-local SAs are exact (suffixes never cross a read).
   Text mode: they are *provisional* near the block tail (a comparison may
   depend on tokens past the block boundary) — which is why phase 3 ranks
   against the resident corpus rather than trusting block order blindly.
3. **Boundary-exact merge via the store** — the block SAs are treated as
   what they are: already-sorted runs (exactly sorted in reads mode, exactly
   sorted away from block tails in text mode).  The default
   ``merge_algorithm = "merge_path"`` merges them by **batched merge-path
   tiles** (:func:`_merge_path_runs`): per tile, every run's next heads are
   fetched in one batched store call and packed into order-preserving key
   words, tie groups deeper than the fetched window are escalated together
   (one batched fetch per extra depth, or a single
   :class:`repro.core.pipeline.DeviceRefiner` call on the device backend),
   and every candidate's output rank is computed at once — the merge-path
   diagonal ranking (``kernels/merge_path`` Pallas kernel under
   ``cfg.use_pallas``, its numpy reference otherwise).  No host loop touches
   individual suffixes: *indexes move, tokens stay put*, and store
   round-trips collapse by the tile width (>= 5x fewer than the heap walk,
   asserted in ``tests/test_merge_path.py``).  Text mode first splits
   off the block-tail *risk set* (suffixes whose block-local comparisons
   could have run past the block boundary) and re-ranks only those; the
   re-ranked pieces join the tile merge as runs of their own.

   ``merge_algorithm = "kway"`` keeps the PR-2 path — splitter ranks located
   inside each run by O(log n) binary-search store comparisons
   (:class:`repro.core.store.WindowCursor` caches each probed window as the
   same packed key words), buckets k-way merged through a host heap — as the
   round-trip baseline; ``"rerank"`` keeps the PR-1 wholesale re-ranking
   merge as the traffic baseline; ``merge_backend = "device"`` runs
   re-rank/risk/tie-group refinement TPU-resident via ``DeviceRefiner``
   (windows served by ``mget_window`` under the same ``shard_map`` reducer
   as the pipeline).

The peak number of records any single run held is reported in
``Footprint.peak_records`` and is bounded by ``plan.capacity_records`` — the
"bounded by store capacity, not by HBM" property the paper claims.
"""
from __future__ import annotations

import contextlib
import heapq
import math
import os
import shutil
import tempfile
import uuid
import warnings
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import numpy as np

from repro.config import SAConfig, SuperblockConfig
from repro.core.integrity import CorruptionError, crc32_array, publish_file
from repro.core.journal import JOURNAL_NAME, BuildJournal, verify_spilled_run
from repro.core.lcp import finish_lcp, pairwise_lcp
from repro.core.pipeline import DeviceRefiner, build_suffix_array
from repro.core.pipeline_exec import PipelineExecutor, pipeline_point
from repro.core.sanitize import (
    SanitizingBackend,
    SanitizingSink,
    check_footprint,
    sanitize_enabled,
    unwrap_backend,
)
from repro.core.store import (
    DEFAULT_CACHE_BUDGET,
    ChunkedFileBackend,
    CorpusStore,
    InMemoryBackend,
    RetryingBackend,
    StoreBackend,
    WindowCursor,
    backend_fingerprint,
    materialize_backend,
)
from repro.core.trace import span
from repro.core.types import WORD_BITS, WORD_MOD, Footprint, SAResult


@dataclass(frozen=True)
class SuperblockPlan:
    """Static partition of a corpus into superblocks."""

    text_mode: bool
    total_records: int
    num_superblocks: int
    capacity_records: int  # record bound for any single run / merge bucket
    blocks: Tuple[Tuple[int, int], ...]  # [lo, hi) token / row ranges
    stride_bits: int


def plan_superblocks(
    corpus_shape, cfg: SAConfig, sb: SuperblockConfig
) -> SuperblockPlan:
    """Derive the superblock split from the capacity knobs.

    ``num_superblocks`` wins if set; otherwise ``max_records_per_run``
    determines the smallest S whose blocks fit; both unset => S = 1
    (single-pass, in-core).

    Granularity floor: a block is at least one item (one read / one token),
    so in reads mode ``capacity_records`` can never go below ``L + 1``
    records.  A budget below that floor is unachievable and triggers a
    warning — ``Footprint.peak_records`` stays bounded by
    ``capacity_records``, not by the raw knob.
    """
    text_mode = len(corpus_shape) == 1
    if text_mode:
        items, per_item = corpus_shape[0], 1
        stride_bits = 0
    else:
        r, l = corpus_shape
        items, per_item = r, l + 1
        stride_bits = int(math.ceil(math.log2(l + 1)))
    total = items * per_item
    if sb.num_superblocks > 0:
        s = sb.num_superblocks
    elif sb.max_records_per_run > 0:
        # derive from whole items per block (a read's records are atomic):
        # ceil(total/budget) alone can overshoot the budget after rounding
        # items up, so size blocks by how many items actually fit.
        items_fit = sb.max_records_per_run // per_item
        s = -(-items // items_fit) if items_fit >= 1 else items
    else:
        s = 1
    s = max(1, min(s, items))
    per_block = -(-items // s)
    blocks = tuple(
        (lo, min(lo + per_block, items))
        for lo in range(0, items, per_block)
    )
    if 0 < sb.max_records_per_run < per_block * per_item:
        if sb.num_superblocks > 0:
            # the budget never shaped this plan: the explicit split overrode
            # it, and that split's blocks are simply bigger than the budget.
            warnings.warn(
                f"max_records_per_run={sb.max_records_per_run} ignored: "
                f"explicit num_superblocks={sb.num_superblocks} yields "
                f"{per_block * per_item} records per block, over the budget",
                stacklevel=2,
            )
        else:
            # the budget drove the split but is unachievable: one item (one
            # read / one token) already exceeds it.
            warnings.warn(
                f"max_records_per_run={sb.max_records_per_run} is below the "
                f"granularity floor ({per_block * per_item} records per "
                "block); peak per-run records will exceed the requested "
                "budget",
                stacklevel=2,
            )
    return SuperblockPlan(
        text_mode=text_mode,
        total_records=total,
        num_superblocks=len(blocks),
        capacity_records=per_block * per_item,
        blocks=blocks,
        stride_bits=stride_bits,
    )


# ---------------------------------------------------------------------------
# store backend resolution + streaming scaffolding
# ---------------------------------------------------------------------------


def corpus_shape_of(corpus) -> Tuple[int, ...]:
    """Corpus shape without materializing it: arrays report their own shape,
    a :class:`StoreBackend` its geometry, a chunked-corpus file path its
    header metadata."""
    if isinstance(corpus, StoreBackend):
        return corpus.shape
    if isinstance(corpus, (str, os.PathLike)):
        from repro.data.chunk_store import read_chunked_corpus_meta

        meta = read_chunked_corpus_meta(os.fspath(corpus))
        return (meta.items,) if meta.text_mode else (meta.items, meta.row_len)
    return np.shape(corpus)


class _Scratch:
    """Private scratch directory for one streaming build (serialized corpus,
    per-block SA spills); removed when the build finishes.

    With an ``executor`` attached (``SuperblockConfig.pipeline_depth >= 1``)
    the spill *write* runs on the background worker: the memmap is created
    immediately (so the caller keeps its disk-backed handle and frees the
    host run right away) but its pages are filled and flushed behind the
    device build of the next block.  Callers must :meth:`drain_spills`
    before the first read of any spilled run — the superblock merge does so
    once between phase 2 and phase 3 and once after re-spilling risk runs.
    """

    def __init__(self, parent: Optional[str],
                 executor: Optional[PipelineExecutor] = None,
                 stable_dir: Optional[str] = None):
        # journaled (resumable) builds use a *stable* scratch path under
        # spill_dir so a resumed attempt finds the previous attempt's runs;
        # per-instance unique spill names keep attempts from colliding.
        if stable_dir is not None:
            os.makedirs(stable_dir, exist_ok=True)
            self.dir = stable_dir
        else:
            self.dir = tempfile.mkdtemp(prefix="sa_superblock_", dir=parent)
        self._n = 0
        self._tag = uuid.uuid4().hex[:8]
        self.spilled_runs = 0
        self.spilled_bytes = 0
        self.executor = executor
        self._pending: List = []
        self.last_spill: Optional[Tuple[str, object]] = None

    def path(self, name: str) -> str:
        return os.path.join(self.dir, name)

    @staticmethod
    def _fill(out: np.ndarray, arr: np.ndarray) -> None:
        out[:] = arr
        out.flush()

    def spill_run(self, arr: np.ndarray) -> np.ndarray:
        """Spill a sorted run to disk and hand back a read-only memmap: the
        run's body is disk-backed, only pages the merge actually touches
        (frontier read-ahead, partition probes) come resident.

        ``last_spill`` records ``(path, task-or-None)`` of this spill so the
        build journal can append the run's completion record once the write
        is observed durable (``task.done()``) — on the main thread, after
        the fact, which keeps journaling out of the worker context."""
        p = self.path(f"run_{self._tag}_{self._n}.npy")
        self._n += 1
        arr = np.ascontiguousarray(arr)
        self.spilled_runs += 1
        self.spilled_bytes += int(arr.size) * arr.dtype.itemsize
        if self.executor is not None:
            out = np.lib.format.open_memmap(
                p, mode="w+", dtype=arr.dtype, shape=arr.shape)
            task = self.executor.submit(self._fill, out, arr)
            self._pending.append(task)
            self.last_spill = (p, task)
            return out
        np.save(p, arr)
        self.last_spill = (p, None)
        return np.load(p, mmap_mode="r")

    def drain_spills(self) -> None:
        """Wait for in-flight spill writes (re-raises a worker failure)."""
        pipeline_point("spill:drain")
        pending, self._pending = self._pending, []
        for task in pending:
            task.result()

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _resolve_backend(
    corpus, cfg: SAConfig, sb: SuperblockConfig, scratch: Optional[_Scratch]
) -> StoreBackend:
    """Build the store backend the whole construction streams through.

    * array + ``store_backend="memory"`` -> :class:`InMemoryBackend` (the
      PR-1/2 behavior, unchanged semantics);
    * array + ``store_backend="chunked"`` -> the array is serialized once to
      the chunked on-disk format in ``scratch`` and served from a
      :class:`ChunkedFileBackend`;
    * path -> :class:`ChunkedFileBackend` over the existing file (never
      host-materialized);
    * an already-constructed :class:`StoreBackend` passes through.

    The chunked backend's LRU gets **half** of ``cache_budget_bytes``; the
    other half covers the merge frontier (read-ahead + tie-depth probes), so
    ``Footprint.peak_resident_bytes`` — cache + frontier — stays under the
    configured budget as a whole.
    """
    if isinstance(corpus, StoreBackend):
        return corpus
    budget = (sb.cache_budget_bytes if sb.cache_budget_bytes > 0
              else DEFAULT_CACHE_BUDGET)
    if isinstance(corpus, (str, os.PathLike)):
        return ChunkedFileBackend(
            os.fspath(corpus), cfg, cache_budget_bytes=budget // 2)
    if sb.store_backend == "memory":
        return InMemoryBackend(corpus, cfg)
    if sb.store_backend != "chunked":
        raise ValueError(f"unknown store_backend: {sb.store_backend!r}")
    from repro.data.chunk_store import chunk_items_for_budget, write_chunked_corpus

    corpus = np.asarray(corpus, np.int32)
    items = corpus.shape[0]
    row_len = 1 if corpus.ndim == 1 else corpus.shape[1]
    chunk_items = sb.chunk_records
    if chunk_items <= 0:
        # several chunks must fit the LRU half-budget or caching degenerates
        chunk_items = chunk_items_for_budget(items, row_len, budget)
    assert scratch is not None
    if sb.write_manifest and sb.spill_dir:
        # the serialized corpus is an index artifact: place it in spill_dir
        # itself (scratch is removed at build end, the index must outlive it)
        path = os.path.join(sb.spill_dir, "corpus.sachunk")
    else:
        path = scratch.path("corpus.sachunk")
    write_chunked_corpus(corpus, path, chunk_items=chunk_items)
    return ChunkedFileBackend(path, cfg, cache_budget_bytes=budget // 2)


@dataclass
class _MergeFrontier:
    """Streaming merge policy: bound the k-way merge's resident frontier.

    ``readahead_bytes`` is split across the live runs of each bucket merge —
    every run head keeps at most that many depth-0 windows prefetched ahead
    of its cursor (batched store rounds), instead of prefetching the whole
    bucket.  ``drop_after_partition`` releases every cached cursor window
    once a bucket partition is located: probe windows are re-fetched by the
    bucket merges that need them, trading bounded traffic for bounded
    residency.
    """

    readahead_bytes: int
    window_bytes: int
    drop_after_partition: bool = True
    # splitter pools are merged with their windows kept hot; bound how many
    # (a too-small pool only coarsens splitters — more recursion, still exact)
    max_pool_windows: int = 64

    def per_run(self, num_runs: int) -> int:
        return max(2, self.readahead_bytes // (max(1, num_runs) * self.window_bytes))

    def per_run_keys(self, num_runs: int, key_words: int,
                     buffers: int = 2) -> int:
        """Merge-path tile width under the same read-ahead budget: tile
        buffers hold *packed* key rows, so the per-element estimate is two
        levels of key words plus the flag lanes (deep-tie escalation can
        widen rows further; the budget's slack share absorbs it).  The
        pipelined merge passes ``buffers=3``: its refill prefetch keeps up
        to one extra tile of pending key rows resident per run, so the tile
        narrows to keep the same byte budget."""
        est = buffers * (key_words + 1) * 4
        return max(2, self.readahead_bytes // (max(1, num_runs) * est))


# ---------------------------------------------------------------------------
# exact suffix comparisons against the resident store
# ---------------------------------------------------------------------------


def _run_starts_np(eq_prev: np.ndarray) -> np.ndarray:
    idx = np.arange(eq_prev.shape[0], dtype=np.int64)
    return np.maximum.accumulate(np.where(eq_prev, -1, idx))


def _tied_np(g: np.ndarray) -> np.ndarray:
    prev = np.concatenate([[-1], g[:-1]])
    nxt = np.concatenate([g[1:], [-2]])
    return (g == prev) | (g == nxt)


def _refine_sort(
    store: CorpusStore, gidx: np.ndarray, cursor: Optional[WindowCursor] = None
) -> np.ndarray:
    """Rank ``gidx`` by exact suffix order with batched store fetches.

    The host port of the device reducer: sort by the first K-token window,
    then refine still-tied groups one window at a time.  Zero-padding past a
    suffix end orders shorter suffixes first, and the global index is the
    final sort key — exactly the oracle's ``(suffix tokens..., index)``
    order.  Capacity overflow retries are group-synchronous: a tie group
    advances a window only when every active member was served.

    ``cursor``: optional :class:`WindowCursor` to warm with every fetched
    window, so a following k-way merge re-serves them from cache instead of
    re-fetching (the text-mode risk re-rank path).
    """
    m = gidx.shape[0]
    if m <= 1:
        return gidx
    k = store.k
    win = store.fetch_windows(gidx, 0)
    if cursor is not None:
        for i in range(m):
            cursor.offer(int(gidx[i]), 0, win[i])
    order = np.lexsort((gidx,) + tuple(win[:, j] for j in range(k - 1, -1, -1)))
    gidx, win = gidx[order], win[order]
    eq = np.concatenate([[False], (win[1:] == win[:-1]).all(axis=1)])
    g = _run_starts_np(eq)
    exhausted = (win == 0).any(axis=1)
    depth = np.ones(m, np.int64)
    # Runaway guard only: every round serves at least the leading tie group
    # (mget_window_host's burst rule), so sum(depth) grows every iteration
    # and m * max-window-depth rounds is a true upper bound even when small
    # request capacities force groups to take turns.
    hard_cap = m * (-(-store.max_len // k) + 2) + 8
    for _ in range(hard_cap):
        tied = _tied_np(g)
        active = tied & ~exhausted
        if not active.any():
            break
        win, ok = store.mget_window_host(gidx, depth, active, g)
        if cursor is not None:
            for i in np.flatnonzero(active & ok):
                cursor.offer(int(gidx[i]), int(depth[i]), win[i])
        # group-synchronous advance (mirrors the device while-loop body)
        member_ok = np.where(active, ok, True)
        starts = np.concatenate([[True], g[1:] != g[:-1]])
        seg_ok = np.logical_and.reduceat(member_ok, np.flatnonzero(starts))
        adv = seg_ok[np.cumsum(starts) - 1] & active
        nk = np.where(adv[:, None], win, 0).astype(np.int32)
        exhausted = np.where(adv, (win == 0).any(axis=1), exhausted)
        depth = np.where(adv, depth + 1, depth)
        order = np.lexsort(
            (gidx,) + tuple(nk[:, j] for j in range(k - 1, -1, -1)) + (g,)
        )
        g, nk = g[order], nk[order]
        gidx, exhausted, depth = gidx[order], exhausted[order], depth[order]
        eq = np.concatenate(
            [[False], (g[1:] == g[:-1]) & (nk[1:] == nk[:-1]).all(axis=1)]
        )
        g = _run_starts_np(eq)
    else:
        raise RuntimeError("superblock merge refinement did not converge")
    return gidx


def _less_than(store: CorpusStore, gidx: np.ndarray, pivot: int) -> np.ndarray:
    """Exact ``suffix(gidx) < suffix(pivot)`` for a batch, ties by index.

    Progressive window comparison; fetched windows for at most one
    capacity-chunk of suffixes are alive at any moment.  The pivot's window
    at each depth is fetched **once** and cached across capacity chunks —
    re-fetching it per chunk would inflate the request/round accounting with
    redundant singletons.
    """
    out = np.zeros(gidx.shape[0], bool)
    cap = store.request_capacity
    cache = {}  # depth -> pivot window, shared by every chunk
    for clo in range(0, gidx.shape[0], cap):
        chunk = gidx[clo : clo + cap]
        res = np.zeros(chunk.shape[0], bool)
        undecided = np.ones(chunk.shape[0], bool)
        depth = 0
        while undecided.any():
            wp = cache.get(depth)
            if wp is None:
                wp = store.fetch_windows(np.array([pivot], np.int64), depth)[0]
                cache[depth] = wp
            sel = np.flatnonzero(undecided)
            ws = store.fetch_windows(chunk[sel], depth)
            neq = ws != wp[None, :]
            anyneq = neq.any(axis=1)
            first = np.argmax(neq, axis=1)
            less = ws[np.arange(sel.size), first] < wp[first]
            res[sel[anyneq]] = less[anyneq]
            undecided[sel[anyneq]] = False
            if (wp == 0).any():
                # equal windows incl. padding => both suffixes ended: the
                # contents are equal and the index breaks the tie.
                eq_sel = sel[~anyneq]
                res[eq_sel] = chunk[eq_sel] < pivot
                undecided[eq_sel] = False
            depth += 1
            assert depth <= store.max_len // store.k + 2, "comparison overran"
        out[clo : clo + cap] = res
    return out


def _partition(
    store: CorpusStore, gidx: np.ndarray, splitters: np.ndarray
) -> List[np.ndarray]:
    """Split ``gidx`` into true-order intervals at the splitter suffixes."""
    bucket = np.zeros(gidx.shape[0], np.int64)
    for pivot in splitters:
        bucket += ~_less_than(store, gidx, int(pivot))
    return [gidx[bucket == b] for b in range(splitters.size + 1)]


def _sorted_runs(
    store: CorpusStore,
    gidx: np.ndarray,
    cap: int,
    samples_per_split: int,
    refine: Callable[[np.ndarray], np.ndarray],
) -> List[np.ndarray]:
    """Fully sort an interval of the true order, in pieces of <= cap records.

    Splitters are member suffixes at sample quantiles, so each part strictly
    shrinks and recursion terminates even on all-equal-content inputs (the
    index tiebreak makes the order strict).  ``refine`` ranks a <= cap batch
    exactly (host :func:`_refine_sort` or the device backend).
    """
    if gidx.size <= cap:
        return [refine(gidx)]
    nb = -(-gidx.size // cap) + 1
    # the sample pool is itself a run: keep it within the record bound
    take = min(gidx.size, cap, max(nb * samples_per_split, nb))
    pos = (np.arange(take, dtype=np.int64) * gidx.size) // take
    sample = refine(gidx[pos])
    splitters = sample[[(i * sample.size) // nb for i in range(1, nb)]]
    out: List[np.ndarray] = []
    for part in _partition(store, gidx, np.unique(splitters)):
        out.extend(_sorted_runs(store, part, cap, samples_per_split, refine))
    return out


# ---------------------------------------------------------------------------
# boundary-exact k-way merge of sorted block runs
# ---------------------------------------------------------------------------


def _rank_in_run(cur: WindowCursor, run: np.ndarray, splitter: int,
                 drop_probes: bool = False) -> int:
    """Number of ``run`` members with suffix < splitter, by binary search.

    ``run`` must be exactly sorted; each probe is one exact store comparison
    (windows cached by the cursor), so locating a splitter costs O(log n)
    comparisons instead of the linear scan of :func:`_less_than` over every
    member.  ``drop_probes`` (streaming mode) releases each probed member's
    windows as soon as the search leaves it — only the splitter's windows
    stay hot across runs, so one search keeps O(tie depth) windows resident
    instead of O(log n · tie depth).
    """
    lo, hi = 0, run.size
    while lo < hi:
        mid = (lo + hi) // 2
        g = int(run[mid])
        if cur.less(g, splitter):
            lo = mid + 1
        else:
            hi = mid
        if drop_probes and g != splitter:
            cur.release(g)
    return lo


def _partition_runs(
    cur: WindowCursor,
    runs: List[np.ndarray],
    splitters: np.ndarray,
    drop_probes: bool = False,
) -> List[List[np.ndarray]]:
    """Cut every sorted run at the splitter ranks.

    Returns ``buckets[b]`` = the per-run segments of merge bucket ``b``;
    segments inherit exact sortedness from their runs, and every member of
    bucket ``b`` precedes every member of bucket ``b+1`` in true suffix
    order (splitters ascend).
    """
    nb = splitters.size + 1
    buckets: List[List[np.ndarray]] = [[] for _ in range(nb)]
    for run in runs:
        cuts = [0]
        for s in splitters:
            cuts.append(max(_rank_in_run(cur, run, int(s), drop_probes),
                            cuts[-1]))
        cuts.append(run.size)
        for b in range(nb):
            seg = run[cuts[b] : cuts[b + 1]]
            if seg.size:
                buckets[b].append(seg)
    return buckets


class _Head:
    """Heap entry of the k-way merge: one run and its cursor position,
    ordered by the exact suffix order of the current head element.

    ``readahead`` > 0 bounds the resident frontier: only the next
    ``readahead`` members' depth-0 windows are batch-prefetched ahead of the
    cursor position (:meth:`ensure_prefetch` refills as the head advances);
    0 means the whole run was prefetched up front (the in-memory default).
    """

    __slots__ = ("cur", "run", "pos", "readahead", "pref_end")

    def __init__(self, cur: WindowCursor, run: np.ndarray, readahead: int = 0):
        self.cur = cur
        self.run = run
        self.pos = 0
        self.readahead = readahead
        self.pref_end = 0
        self.ensure_prefetch()

    def ensure_prefetch(self) -> None:
        if self.readahead and self.pos >= self.pref_end:
            self.pref_end = min(self.pos + self.readahead, self.run.size)
            self.cur.prefetch(np.asarray(self.run[self.pos:self.pref_end],
                                         np.int64))

    @property
    def gidx(self) -> int:
        return int(self.run[self.pos])

    def __lt__(self, other: "_Head") -> bool:
        return self.cur.less(self.gidx, other.gidx)


def _kway_merge(
    cur: WindowCursor,
    runs: List[np.ndarray],
    release: bool = True,
    frontier: Optional[_MergeFrontier] = None,
) -> np.ndarray:
    """Merge exactly-sorted runs with a heap of run heads.

    Without a ``frontier`` every member's depth-0 window is prefetched in
    one batched store round (the in-memory default); with one, each run
    keeps only a bounded read-ahead of windows resident — batched refills as
    heads advance, so store rounds stay amortized while the frontier stays
    within the residency budget.  Head-vs-head comparisons hit the cursor
    cache and deepen only to actual tie-breaking depth.  Emitted suffixes
    release their windows (unless the caller wants them kept hot — splitter
    pools are re-probed by the partition right after), so the resident
    working set shrinks as the merge drains.
    """
    runs = [r for r in runs if r.size]
    if not runs:
        return np.zeros((0,), np.int64)
    if len(runs) == 1:
        return runs[0]
    total = sum(r.size for r in runs)
    if frontier is None:
        cur.prefetch(np.concatenate(runs))
        heap = [_Head(cur, r) for r in runs]
    else:
        per_run = frontier.per_run(len(runs))
        heap = [_Head(cur, r, readahead=per_run) for r in runs]
    heapq.heapify(heap)
    out = np.empty(total, np.int64)
    i = 0
    while heap:
        h = heapq.heappop(heap)
        g = h.gidx
        out[i] = g
        i += 1
        if release:
            cur.release(g)
        h.pos += 1
        if h.pos < h.run.size:
            h.ensure_prefetch()
            heapq.heappush(heap, h)
    return out


def _merge_runs(
    cur: WindowCursor,
    runs: List[np.ndarray],
    cap: int,
    samples_per_split: int,
    rank_pool: Callable[[List[np.ndarray]], np.ndarray],
    frontier: Optional[_MergeFrontier] = None,
) -> List[np.ndarray]:
    """Merge exactly-sorted runs into <= cap pieces of the true order.

    Buckets whose total fits the record bound are k-way merged directly;
    oversized buckets recurse: splitters are member suffixes at per-run
    quantiles, located inside every run by binary search, and — the index
    tiebreak making suffix order strict — every split is guaranteed to shed
    at least one member per side, so the recursion terminates even on
    all-equal-content input.

    ``rank_pool`` ranks the splitter sample (a list of per-run pick
    subsequences, each inheriting exact sortedness from its run) — k-way
    merged through the shared cursor, so the pool's windows are fetched once
    and stay hot for the partition probes and the final bucket merges.

    A ``frontier`` (streaming mode) bounds what any of this keeps resident:
    bucket merges read ahead instead of prefetching whole buckets, and the
    cursor cache is dropped once a partition is located
    (``drop_after_partition`` — probe windows re-fetch on demand).
    """
    runs = [r for r in runs if r.size]
    total = sum(r.size for r in runs)
    if total == 0:
        return []
    if total <= cap:
        return [_kway_merge(cur, runs, frontier=frontier)]
    nb = -(-total // cap) + 1
    take = min(total, cap, max(nb * samples_per_split, nb))
    if frontier is not None:
        # pool windows stay hot through the partition: bound their residency
        take = min(take, max(nb, frontier.max_pool_windows))
    pos = (np.arange(take, dtype=np.int64) * total) // take
    # evenly spaced picks over the concatenated runs = per-run quantiles;
    # regroup them per run so each pick subsequence is itself a sorted run.
    bounds = np.cumsum([0, *(r.size for r in runs)])
    pool_runs = []
    for ri, run in enumerate(runs):
        sel = pos[(pos >= bounds[ri]) & (pos < bounds[ri + 1])] - bounds[ri]
        if sel.size:
            pool_runs.append(run[sel])
    pool = rank_pool(pool_runs)
    picks = pool[[(i * pool.size) // nb for i in range(1, nb)]]
    buckets = _partition_runs(cur, runs, picks,
                              drop_probes=frontier is not None)
    if frontier is not None and frontier.drop_after_partition:
        cur.release_all()  # probe/pool windows re-fetch on demand, bounded
    out: List[np.ndarray] = []
    for segs in buckets:
        sub_total = sum(s.size for s in segs)
        if sub_total >= total:
            raise RuntimeError("superblock k-way partition made no progress")
        out.extend(_merge_runs(cur, segs, cap, samples_per_split, rank_pool,
                               frontier=frontier))
    return out


# ---------------------------------------------------------------------------
# merge-path: batched, device-resident k-way merge (no host heap walk)
# ---------------------------------------------------------------------------


class _OutputSink:
    """Final-order SA emitter.

    Merge pieces arrive in true suffix order, so the output can be written
    sequentially instead of concatenated at the end: into a preallocated host
    array by default, or — when ``SuperblockConfig.spill_dir`` is set — into
    a disk-backed ``.npy`` memmap, dropping the last O(n) host allocation
    (the returned ``SAResult.suffix_array`` is then the memmap itself).

    With ``pair_lcp`` set (``SuperblockConfig.emit_lcp``) the sink also
    produces the adjacent-pair LCP array as a side effect of emission: emit
    order *is* final order, so ``lcp[i]`` is exactly one compare between
    consecutive emitted suffixes — including across piece seams, via the
    carried-over last index of the previous piece.  Batched internally so a
    whole-run passthrough piece never materializes O(n) windows at once.
    """

    _LCP_BATCH = 1 << 16

    def __init__(self, total: int, memmap_path: Optional[str] = None,
                 lcp_path: Optional[str] = None, pair_lcp=None,
                 executor: Optional[PipelineExecutor] = None):
        self.total = int(total)
        self.written = 0
        self.pieces = 0
        self.max_piece = 0
        self.path = memmap_path
        # with an executor the SA-array writes run on the background worker
        # (emitted pieces are freshly allocated, so hand-off is safe; FIFO
        # submission order preserves write order).  LCP emission stays on
        # the caller thread: it is store traffic, and the store belongs to
        # the merge loop.  ``result`` drains the writer before the
        # flush + rename, so fsync/rename semantics are unchanged.
        self._exec = executor
        self._tasks: List = []
        self._finalized = False
        if memmap_path is not None:
            # write under a unique temp name and atomically rename on
            # completion: reusing a spill_dir must never truncate the inode
            # a previous build's returned memmap is still mapping — and two
            # concurrent builds sharing a spill_dir must not share a tmp.
            self._tmp = f"{memmap_path}.{os.getpid()}.{uuid.uuid4().hex}.tmp"
            self._out = np.lib.format.open_memmap(
                self._tmp, mode="w+", dtype=np.int64, shape=(self.total,))
        else:
            self._out = np.empty(self.total, np.int64)
        self._pair_lcp = pair_lcp
        self.lcp_path = lcp_path if pair_lcp is not None else None
        self._last: Optional[int] = None  # last emitted gidx (seam compare)
        self._lcp: Optional[np.ndarray] = None
        if pair_lcp is not None:
            if self.lcp_path is not None:
                self._lcp_tmp = (
                    f"{self.lcp_path}.{os.getpid()}.{uuid.uuid4().hex}.tmp")
                self._lcp = np.lib.format.open_memmap(
                    self._lcp_tmp, mode="w+", dtype=np.int64,
                    shape=(self.total,))
            else:
                self._lcp = np.empty(self.total, np.int64)

    def append(self, piece: np.ndarray) -> None:
        m = int(piece.shape[0])
        if m == 0:
            return
        pipeline_point("sink:append")
        if self._pair_lcp is not None:
            self._append_lcp(piece)
        if self._exec is not None:
            self._tasks.append(
                self._exec.submit(self._write, self.written, piece))
        else:
            self._out[self.written : self.written + m] = piece
        self.written += m
        self.pieces += 1
        self.max_piece = max(self.max_piece, m)

    def _write(self, lo: int, piece: np.ndarray) -> None:
        self._out[lo : lo + piece.shape[0]] = piece

    def _append_lcp(self, piece: np.ndarray) -> None:
        p = np.asarray(piece)  # memmap pieces stay views, batches copy below
        m = int(p.shape[0])
        base = self.written
        start = 0
        if self._last is None:
            self._lcp[base] = 0  # lcp[0] has no left neighbor
            start = 1
        for lo in range(start, m, self._LCP_BATCH):
            hi = min(lo + self._LCP_BATCH, m)
            right = np.asarray(p[lo:hi], np.int64)
            left = np.empty(hi - lo, np.int64)
            left[1:] = right[:-1]
            left[0] = int(p[lo - 1]) if lo > 0 else self._last
            self._lcp[base + lo : base + hi] = self._pair_lcp(left, right)
        self._last = int(p[-1])

    def result(self) -> np.ndarray:
        assert self.written == self.total, (self.written, self.total)
        self._drain()
        self._finalized = True
        if self.lcp_path is not None:
            self._lcp.flush()
            del self._lcp
            publish_file(self._lcp_tmp, self.lcp_path)
            self._lcp = np.load(self.lcp_path, mmap_mode="r+")
        if self.path is not None:
            self._out.flush()
            del self._out  # drop the write mapping before the rename
            publish_file(self._tmp, self.path)
            self._out = np.load(self.path, mmap_mode="r+")
        return self._out

    def _drain(self) -> None:
        """Wait for in-flight background writes (re-raises a write failure)."""
        tasks, self._tasks = self._tasks, []
        for t in tasks:
            t.result()

    def abort(self) -> None:
        """Failure path: wait out in-flight writes, drop the write mappings,
        and unlink the tmp files so a failed build leaves no orphaned
        ``.tmp`` memmaps in ``spill_dir``.  No-op after :meth:`result`."""
        if self._finalized:
            return
        tasks, self._tasks = self._tasks, []
        for t in tasks:
            with contextlib.suppress(BaseException):
                t.result()
        if self.path is not None:
            self._out = None
            with contextlib.suppress(OSError):
                os.unlink(self._tmp)
        if self.lcp_path is not None:
            self._lcp = None
            with contextlib.suppress(OSError):
                os.unlink(self._lcp_tmp)

    @property
    def lcp(self) -> Optional[np.ndarray]:
        """The emitted LCP array (None unless built with ``pair_lcp``);
        valid after :meth:`result`."""
        return self._lcp


class _JournalingSink:
    """Thin tee around the output sink: every emitted piece appends a merge
    watermark record to the build journal (non-durable, batched fsync — the
    merge phase is redone wholesale on resume, so the watermark is
    observability and torn-tail test surface, not a unit of recovery).
    Everything else delegates to the wrapped sink."""

    def __init__(self, inner, journal: BuildJournal):
        self.inner = inner
        self.journal = journal
        self._emitted = 0

    def append(self, piece: np.ndarray) -> None:
        self.inner.append(piece)
        self._emitted += int(np.asarray(piece).shape[0])
        self.journal.append({"t": "emit", "rows": self._emitted},
                            durable=False)

    def __getattr__(self, name: str):
        return getattr(self.inner, name)


class _RunTile:
    """One sorted run's buffered frontier for the merge-path tile merge.

    Holds up to ``tile`` unconsumed run members with their packed key words
    (``levels * key_words`` columns; deeper levels are appended by the tie
    escalation and persist until the member is emitted, so every (suffix,
    depth) window is fetched once), per-member fetched-level counts and
    end-of-suffix flags.  Columns past a member's fetched level are zeros —
    exactly the zero-padding a finished suffix really continues with, and
    never consulted for an unfinished one (the escalation fetches a level
    for every group member before comparing it).
    """

    __slots__ = ("run", "pos", "count", "words", "levels", "ended", "kw",
                 "pend_keys", "pend_ended")

    def __init__(self, run: np.ndarray, kw: int):
        self.run = run
        self.kw = kw
        self.pos = 0  # consumed members
        self.count = 0  # buffered members
        self.words = np.zeros((0, kw), np.int32)
        self.levels = np.zeros((0,), np.int32)  # fetched levels per member
        self.ended = np.zeros((0,), bool)
        # prefetched depth-0 keys for run[pos+count : pos+count+pending]:
        # ``consume`` leaves ``pos + count`` invariant, so rows prefetched
        # during ranking stay valid whatever the emit horizon turns out to
        # be — the next refill serves its prefix from here instead of the
        # store (each run position's depth-0 window is fetched exactly once,
        # pipelined or not).
        self.pend_keys = np.zeros((0, kw), np.int32)
        self.pend_ended = np.zeros((0,), bool)

    @property
    def pending(self) -> int:
        return int(self.pend_keys.shape[0])

    @property
    def remaining(self) -> int:
        return int(self.run.size) - self.pos

    @property
    def buffered(self) -> int:
        return self.count

    @property
    def gidx(self) -> np.ndarray:
        """Buffered members' global indexes — a transient view into the
        (possibly disk-spilled) run itself, not a resident copy."""
        return np.asarray(self.run[self.pos : self.pos + self.count], np.int64)

    def need(self, tile: int) -> np.ndarray:
        """Run members to fetch so the buffer covers min(tile, remaining)
        (members already prefetched into the pending buffer excluded)."""
        want = min(tile, self.remaining) - self.count - self.pending
        if want <= 0:
            return np.zeros((0,), np.int64)
        lo = self.pos + self.count + self.pending
        return np.asarray(self.run[lo : lo + want], np.int64)

    def prefetch_need(self, tile: int) -> np.ndarray:
        """Run members whose depth-0 keys the *next* refill could possibly
        ask for: however many members emit consumes, the next window starts
        at the invariant ``pos + count`` and covers at most
        ``min(tile, remaining - count)`` members, so prefetching up to there
        never fetches a key the synchronous path would not."""
        cap = min(tile, self.remaining - self.count) - self.pending
        if cap <= 0:
            return np.zeros((0,), np.int64)
        lo = self.pos + self.count + self.pending
        return np.asarray(self.run[lo : lo + cap], np.int64)

    def admit_pending(self, keys: np.ndarray, ended: np.ndarray) -> None:
        if keys.shape[0] == 0:
            return
        self.pend_keys = np.concatenate([self.pend_keys, keys])
        self.pend_ended = np.concatenate(
            [self.pend_ended, np.asarray(ended, bool)])

    def admit(self, keys: np.ndarray, ended: np.ndarray, tile: int) -> None:
        """Refill: queue freshly fetched rows behind the pending buffer,
        then move the head of the pending buffer into the live tile."""
        self.admit_pending(keys, ended)
        take = min(min(tile, self.remaining) - self.count, self.pending)
        if take > 0:
            self.extend(self.pend_keys[:take], self.pend_ended[:take])
            self.pend_keys = self.pend_keys[take:]
            self.pend_ended = self.pend_ended[take:]

    def extend(self, keys: np.ndarray, ended: np.ndarray) -> None:
        m = keys.shape[0]
        if m == 0:
            return
        width = self.words.shape[1]
        rows = np.zeros((m, width), np.int32)
        rows[:, : self.kw] = keys
        self.count += m
        self.words = np.concatenate([self.words, rows])
        self.levels = np.concatenate([self.levels, np.ones(m, np.int32)])
        self.ended = np.concatenate([self.ended, np.asarray(ended, bool)])

    def widen(self, levels: int) -> None:
        """Grow the word matrix to ``levels * key_words`` columns (zeros)."""
        width = levels * self.kw
        if self.words.shape[1] >= width:
            return
        grown = np.zeros((self.words.shape[0], width), np.int32)
        grown[:, : self.words.shape[1]] = self.words
        self.words = grown

    def consume(self, count: int) -> None:
        self.pos += count
        self.count -= count
        self.words = self.words[count:]
        self.levels = self.levels[count:]
        self.ended = self.ended[count:]

    @property
    def nbytes(self) -> int:
        return int(self.words.nbytes + self.levels.nbytes + self.ended.nbytes
                   + self.pend_keys.nbytes + self.pend_ended.nbytes)


def _group_ids(prev: Optional[np.ndarray], cols: np.ndarray) -> np.ndarray:
    """Equality-group ids of rows under (previous group, cols...)."""
    keys = tuple(cols[:, w] for w in range(cols.shape[1] - 1, -1, -1))
    if prev is not None:
        keys = keys + (prev,)
    order = np.lexsort(keys)
    stacked = cols[order]
    new = np.ones(order.shape[0], bool)
    if order.shape[0] > 1:
        same = (stacked[1:] == stacked[:-1]).all(axis=1)
        if prev is not None:
            same &= prev[order][1:] == prev[order][:-1]
        new[1:] = ~same
    gid = np.empty(order.shape[0], np.int64)
    gid[order] = np.cumsum(new) - 1
    return gid


def _merge_path_runs(
    store: CorpusStore,
    runs: List[np.ndarray],
    sink: _OutputSink,
    cap: int,
    merge_tile: int,
    use_pallas: bool,
    refiner: Optional[DeviceRefiner] = None,
    frontier: Optional[_MergeFrontier] = None,
    executor: Optional[PipelineExecutor] = None,
    timers: Optional[dict] = None,
) -> int:
    """Merge exactly-sorted runs by merge-path tiles; emit in final order.

    The heap walk's per-suffix cursor pokes are replaced by batched tile
    rounds: per tile, the next ``tile`` members of every run are fetched in
    **one** batched store call and packed to key words; groups of candidates
    whose fetched words tie are escalated together — one batched fetch per
    extra window depth (or one :class:`DeviceRefiner` call resolving every
    group at once) instead of one store round per comparison; then every
    candidate's output rank is computed in one shot (``kernels/merge_path``
    Pallas kernel when ``cfg.use_pallas``, else the numpy reference
    ``CorpusStore.rank_windows`` — same packed-word compare either way).
    All candidates ranked below every partially-buffered run's last buffered
    member are emitted at once (the merge-path safety horizon), so a tile
    usually drains far more than ``tile`` suffixes per round trip.

    Each phase of a tile is a span (``merge.refill``, ``merge.escalate``,
    ``merge.rank``, ``merge.collect``, ``merge.emit``) whose seconds add to
    ``timers["t_merge_<phase>_s"]`` when ``timers`` is given.

    Returns the peak candidate count (the merge's record footprint).
    """
    runs = [np.asarray(r) for r in runs if r.size]
    if not runs:
        return 0
    if len(runs) == 1:
        sink.append(np.asarray(runs[0], np.int64))
        return int(runs[0].size)
    kw = store.key_words
    if merge_tile > 0:  # explicit knob wins, streaming or not
        tile = merge_tile
    elif frontier is not None:
        tile = frontier.per_run_keys(
            len(runs), kw, buffers=3 if executor is not None else 2)
    else:
        tile = 4096
    tile = max(2, min(tile, cap // max(1, len(runs))))
    tiles = [_RunTile(r, kw) for r in runs]
    registered = 0  # frontier bytes currently registered with the store
    peak_candidates = 0
    max_levels = store.max_window_depth

    def _account() -> int:
        nonlocal registered
        cur = sum(t.nbytes for t in tiles)
        store.add_frontier(cur - registered)
        registered = cur
        return cur

    while any(t.buffered or t.remaining for t in tiles):
        # ---- refill: one batched store round for every run's new heads
        # (heads already prefetched into a tile's pending buffer are served
        # from there; only the remainder touches the store) ----
        with span("merge.refill", timers, "t_merge_refill_s"):
            pipeline_point("merge:refill")
            needs = [t.need(tile) for t in tiles]
            flat = np.concatenate(needs)
            keys = ended = None
            if flat.size:
                keys, ended = store.fetch_keys(flat, 0)
            off = 0
            empty_k = np.zeros((0, kw), np.int32)
            empty_e = np.zeros((0,), bool)
            for t, n in zip(tiles, needs, strict=True):
                if n.size:
                    t.admit(keys[off : off + n.size], ended[off : off + n.size],
                            tile)
                else:
                    t.admit(empty_k, empty_e, tile)
                off += n.size
            _account()  # register the refill before escalation fetches, so
            # LRU-loading rounds see the full frontier in peak_resident
        live = [t for t in tiles if t.buffered]
        cand_gidx = np.concatenate([t.gidx for t in live])
        c = cand_gidx.shape[0]
        peak_candidates = max(peak_candidates, c)

        # ---- escalate ties: whole groups per round, batched fetches -------
        with span("merge.escalate", timers, "t_merge_escalate_s"):
            level = 1
            width = max(t.words.shape[1] for t in live) // kw
            g = None
            tie_col = None
            while True:
                for t in live:
                    t.widen(max(level, width))
                cand_words = np.concatenate([t.words for t in live])
                cand_levels = np.concatenate([t.levels for t in live])
                cand_ended = np.concatenate([t.ended for t in live])
                lo = (level - 1) * kw
                g = _group_ids(g, cand_words[:, lo : lo + kw])
                sizes = np.bincount(g)
                open_grp = np.zeros(sizes.shape[0], bool)
                np.logical_or.at(open_grp, g, ~cand_ended)
                amb = (sizes[g] >= 2) & open_grp[g]
                if not amb.any():
                    break
                if refiner is not None:
                    # one device refinement resolves every tie group at once:
                    # a member's position in the refined order is decisive
                    # within its group and never consulted across groups.
                    members = np.flatnonzero(amb)
                    order = refiner.refine(cand_gidx[members])
                    # vectorized rank lookup: member i's tie word = its position
                    # in the refined order (no per-suffix host loop)
                    so = np.argsort(order)
                    tie_col = np.zeros(c, np.int32)
                    tie_col[members] = so[
                        np.searchsorted(order[so], cand_gidx[members])
                    ].astype(np.int32)
                    break
                if level >= max_levels:
                    raise RuntimeError("merge-path escalation overran the "
                                       "window bound")
                # fetch the next window level for unfinished members of open
                # groups (finished members' deeper words are genuine zeros)
                fetch = np.flatnonzero(amb & ~cand_ended & (cand_levels <= level))
                if fetch.size:
                    keys, ended = store.fetch_keys(cand_gidx[fetch], level)
                    bounds = np.cumsum([0, *(t.buffered for t in live)])
                    t_of = np.searchsorted(bounds, fetch, side="right") - 1
                    for ti, t in enumerate(live):
                        sel = fetch[t_of == ti]
                        if not sel.size:
                            continue
                        local = sel - bounds[ti]
                        t.widen(level + 1)
                        t.words[local, level * kw : (level + 1) * kw] = (
                            keys[t_of == ti])
                        t.levels[local] = level + 1
                        t.ended[local] |= ended[t_of == ti]
                level += 1
            _account()

        # ---- prefetch the next refill while this tile ranks ---------------
        # The store is quiescent during ranking (the Pallas kernel runs on
        # device, the numpy reference is a pure lexsort), so the background
        # worker owns the *backend* for exactly this window: one batched
        # depth-0 gather_keys — the unaccounted worker-safe half of
        # fetch_keys — collected below *before* emit (whose pair-LCP /
        # audit traffic touches the store again).  FetchStats accounting
        # happens on the main thread at collection (note_fetched; salint
        # SAL010), so positions are served and accounted once either way —
        # byte and request totals match the synchronous path.
        pf_task = pf_needs = None
        if executor is not None:
            pf_needs = [t.prefetch_need(tile) for t in tiles]
            pf_flat = np.concatenate(pf_needs)
            if pf_flat.size:
                pf_task = executor.submit(store.gather_keys, pf_flat, 0)

        # ---- rank the tile: merge-path diagonal ranks in one shot ---------
        with span("merge.rank", timers, "t_merge_rank_s"):
            pipeline_point("merge:rank")
            cand_words = np.concatenate([t.words for t in live])
            if tie_col is not None:
                cand_words = np.concatenate([cand_words, tie_col[:, None]], axis=1)
            if use_pallas:
                from repro.kernels import ops as kops

                idx_hi = (cand_gidx >> WORD_BITS).astype(np.int32)
                idx_lo = (cand_gidx & (WORD_MOD - 1)).astype(np.int32)
                keys_full = np.concatenate(
                    [cand_words, idx_hi[:, None], idx_lo[:, None]], axis=1)
                ranks = kops.merge_path_ranks(keys_full).astype(np.int64)
            else:
                ranks = store.rank_windows(cand_words, cand_gidx)

        # ---- collect the prefetched refill (store is ours again) ----------
        if pf_task is not None:
            with span("merge.collect", timers, "t_merge_collect_s"):
                pipeline_point("merge:collect")
                pf_keys, pf_ended = pf_task.result()
                store.note_fetched(pf_keys.shape[0])  # main-thread accounting
                off = 0
                for t, n in zip(tiles, pf_needs, strict=True):
                    t.admit_pending(pf_keys[off : off + n.size],
                                    pf_ended[off : off + n.size])
                    off += n.size
                _account()

        # ---- emit everything below the safety horizon ---------------------
        with span("merge.emit", timers, "t_merge_emit_s"):
            pipeline_point("merge:emit")
            bounds = np.cumsum([0, *(t.buffered for t in live)])
            emit_cnt = c
            for ti, t in enumerate(live):
                if t.remaining > t.buffered:  # partially buffered run
                    emit_cnt = min(emit_cnt, int(ranks[bounds[ti + 1] - 1]) + 1)
            emitted = np.empty(emit_cnt, np.int64)
            take = ranks < emit_cnt
            emitted[ranks[take]] = cand_gidx[take]
            sink.append(emitted)
            for ti, t in enumerate(live):
                t.consume(int(np.count_nonzero(take[bounds[ti] : bounds[ti + 1]])))
            _account()
    store.add_frontier(-registered)
    return peak_candidates


def _split_boundary_risk(
    plan: SuperblockPlan,
    local_sas: List[np.ndarray],
    block_stats: List[dict],
    k: int,
) -> Tuple[List[np.ndarray], np.ndarray]:
    """Text mode: split each block's run into its exactly-sorted part and the
    block-boundary *risk set*.

    A text-mode block build compares suffixes against the block's own tokens
    only, treating the block end as end-of-text.  A suffix whose comparisons
    never ran past the boundary is ordered by genuine global tokens, so the
    block-local order of those suffixes is globally exact.  The build
    examines at most ``rounds * K`` tokens per suffix (``rounds`` is the max
    refinement depth reported by the block's pipeline run), so suffixes
    further than that from the block end are safe; the rest — and whole
    blocks that hit the refinement hard cap (``unresolved > 0``) — must be
    re-ranked against the resident store.  The final block ends at the true
    text end: nothing in it is at risk.
    """
    runs: List[np.ndarray] = []
    risk: List[np.ndarray] = []
    last = len(plan.blocks) - 1
    for bi, ((_, hi), sa_b) in enumerate(zip(plan.blocks, local_sas,
                                             strict=True)):
        if bi == last:
            runs.append(sa_b)
            continue
        if block_stats[bi].get("unresolved", 0):
            risk.append(sa_b)  # block order unproven: re-rank the whole block
            continue
        reach = block_stats[bi]["rounds"] * k
        keep = (hi - sa_b) > reach
        runs.append(sa_b[keep])
        risk.append(sa_b[~keep])
    riskv = np.concatenate(risk) if risk else np.zeros((0,), np.int64)
    return [r for r in runs if r.size], riskv


# ---------------------------------------------------------------------------
# the out-of-core build
# ---------------------------------------------------------------------------

# build stats of the out-of-core phases' spans, in seconds: staging, block
# builds (the device pipeline's share of them), the merge, its phases and
# the store's window fetches on the main thread (all of them in the merge)
_TIMERS = ("t_stage_s", "t_build_s", "t_device_s", "t_merge_s",
           "t_merge_refill_s", "t_merge_escalate_s", "t_merge_rank_s",
           "t_merge_collect_s", "t_merge_emit_s", "t_fetch_s")


def build_suffix_array_superblock(
    corpus,
    lengths=None,
    cfg: SAConfig = SAConfig(),
    sb: SuperblockConfig = SuperblockConfig(),
    mesh=None,
) -> SAResult:
    """Out-of-core SA build: per-superblock pipeline runs + store-mediated
    merge.  Falls back to the single-pass pipeline when one block suffices.

    ``corpus`` may be an array, a chunked-corpus file path, or a
    :class:`repro.core.store.StoreBackend`.  With the chunked backend
    (``sb.store_backend="chunked"`` or a file path) the build is
    out-of-*host-RAM*: corpus bytes stay on disk behind a budgeted LRU chunk
    cache, each superblock stages only its own item range for its pipeline
    run, block SAs spill to disk, and the merge keeps a bounded read-ahead
    frontier — ``Footprint.peak_resident_bytes`` (cache + frontier) stays
    under ``sb.cache_budget_bytes``.
    """
    # a scratch dir is needed whenever the build streams (serialized corpus
    # and/or per-block SA spills) — and always under the journaled resumable
    # regime, where block runs spill on *every* backend so a resumed build
    # has something durable to pick up.
    journaled = sb.resume and sb.spill_dir is not None
    needs_scratch = (
        isinstance(corpus, (str, os.PathLike))
        or (isinstance(corpus, StoreBackend)
            and not isinstance(corpus, InMemoryBackend))
        or (not isinstance(corpus, StoreBackend)
            and sb.store_backend == "chunked")
        or journaled
    )
    if sb.spill_dir is not None:
        os.makedirs(sb.spill_dir, exist_ok=True)
    if journaled:
        # a killed attempt cannot clean up after itself: sweep its orphaned
        # publish tmps (the journal + scratch runs are NOT tmps and survive)
        for orphan in os.listdir(sb.spill_dir):
            if orphan.endswith((".tmp", ".tmp.npy")):
                with contextlib.suppress(OSError):
                    os.unlink(os.path.join(sb.spill_dir, orphan))
        scratch = _Scratch(sb.spill_dir,
                           stable_dir=os.path.join(sb.spill_dir, "scratch"))
    else:
        scratch = _Scratch(sb.spill_dir) if needs_scratch else None
    backend: Optional[StoreBackend] = None
    owns_backend = True
    ok = False
    try:
        corpus_timer: dict = {}  # the corpus written to the chunked format
        with span("build.corpus", corpus_timer, "t_corpus_s"):
            backend = _resolve_backend(corpus, cfg, sb, scratch)
        owns_backend = backend is not corpus  # decided before any wrapping
        if sb.store_retries > 0:
            backend = RetryingBackend(backend, retries=sb.store_retries,
                                      backoff_s=sb.store_backoff_s)
        if sanitize_enabled(sb):
            backend = SanitizingBackend(backend)
        res = _build_superblock(
            backend, lengths, cfg, sb, mesh, scratch,
            original_corpus=corpus,
        )
        res.stats.update(corpus_timer)
        ok = True
        return res
    finally:
        if backend is not None and owns_backend:
            backend.close()
        if scratch is not None:
            if getattr(scratch, "_journal", None) is not None:
                scratch._journal.close()  # flushed; kept on disk for resume
            if ok or not journaled:
                # a failed journaled build keeps scratch + journal: that IS
                # the resumable state the next --resume attempt picks up
                scratch.cleanup()


def _build_superblock(
    backend: StoreBackend,
    lengths,
    cfg: SAConfig,
    sb: SuperblockConfig,
    mesh,
    scratch: Optional[_Scratch],
    original_corpus,
) -> SAResult:
    """Executor-lifecycle wrapper around the phased build.

    ``sb.pipeline_depth >= 1`` attaches one background worker
    (:class:`repro.core.pipeline_exec.PipelineExecutor`) that the three
    overlaps share — block-staging prefetch, spill/output writes, merge
    refill prefetch.  The wrapper owns its deterministic shutdown: on
    success the executor is drained and joined (re-raising any unobserved
    worker failure); on any failure the output sink's tmp memmaps are
    unlinked and the worker is still joined before the original exception
    propagates.
    """
    pipe: Optional[PipelineExecutor] = None
    if sb.pipeline_depth > 0:
        pipe = PipelineExecutor(depth=sb.pipeline_depth, name="sa-pipeline")
    if scratch is not None:
        scratch.executor = pipe
    sinks: List[_OutputSink] = []  # parked here so the failure path can
    # remove tmp memmaps whichever phase raised
    try:
        res = _build_superblock_phases(
            backend, lengths, cfg, sb, mesh, scratch, original_corpus,
            pipe, sinks,
        )
    except BaseException:
        for s in sinks:
            with contextlib.suppress(BaseException):
                s.abort()
        if pipe is not None:
            with contextlib.suppress(BaseException):
                pipe.close()
        raise
    if pipe is not None:
        pipe.close()
    return res


def _build_superblock_phases(
    backend: StoreBackend,
    lengths,
    cfg: SAConfig,
    sb: SuperblockConfig,
    mesh,
    scratch: Optional[_Scratch],
    original_corpus,
    pipe: Optional[PipelineExecutor],
    sinks: List["_OutputSink"],
) -> SAResult:
    if sb.write_manifest and not sb.spill_dir:
        raise ValueError(
            "write_manifest needs spill_dir: the manifest finalizes that "
            "directory as the reopenable index"
        )
    plan = plan_superblocks(backend.shape, cfg, sb)
    if plan.num_superblocks <= 1:
        store = CorpusStore(None, cfg, backend=backend,
                            request_capacity=sb.request_capacity)
        res = build_suffix_array(
            store.stage_items(0, backend.n), lengths=lengths, cfg=cfg,
            mesh=mesh, lcp=sb.emit_lcp,
        )
        # the device named the LCP of every pair its refinement split; the
        # host fetches the rest.  The index directory (when asked for) is
        # written wholesale.
        if sb.emit_lcp:
            with span("build.lcp", res.stats, "t_lcp_s"):
                res.stats["lcp_host_pairs"] = finish_lcp(
                    store, res.suffix_array, res.lcp)
            res.stats["emit_lcp"] = True
        if sb.write_manifest:
            _write_index_manifest(res, backend, cfg, sb, scratch)
        return res
    if sb.merge_backend not in ("host", "device"):
        raise ValueError(f"unknown merge_backend: {sb.merge_backend!r}")
    if sb.merge_algorithm not in ("merge_path", "kway", "rerank"):
        raise ValueError(f"unknown merge_algorithm: {sb.merge_algorithm!r}")
    streaming = not isinstance(unwrap_backend(backend), InMemoryBackend)
    if streaming and sb.merge_backend == "device":
        raise ValueError(
            "merge_backend='device' needs the corpus HBM-resident; "
            "use store_backend='memory' (the chunked backend exists to keep "
            "the corpus off-host, which the device refiner cannot serve)"
        )
    assert not streaming or scratch is not None  # wrapper provides it

    # ---- build journal: resumable unit-of-recovery bookkeeping ---------
    # sb.resume + spill_dir arm an fsync'd append-only journal next to the
    # stable scratch dir.  Completed block runs (with content crcs) are
    # journaled as they become durable; re-entering the build replays the
    # journal and skips every verified-complete block.  The merge phase is
    # always redone from the preserved runs — runs are the unit of
    # recovery, emission is cheap relative to block builds.
    jr: Optional[BuildJournal] = None
    resumed: dict = {}
    journal_hits = 0
    if sb.resume and sb.spill_dir is not None and scratch is not None:
        jpath = os.path.join(sb.spill_dir, JOURNAL_NAME)
        fp_rec = dict(backend_fingerprint(backend))
        fp_rec.update(superblocks=int(plan.num_superblocks),
                      capacity=int(plan.capacity_records),
                      merge_algorithm=sb.merge_algorithm,
                      emit_lcp=bool(sb.emit_lcp))
        records = BuildJournal.load(jpath)  # CorruptionError on bad interior
        if records:
            first = records[0]
            if first.get("t") != "begin":
                raise CorruptionError(
                    "build journal", detail="first record is not 'begin'",
                    path=jpath)
            if first.get("fp") != fp_rec:
                raise ValueError(
                    "resume refused: the journal in spill_dir belongs to a "
                    "different build (corpus/plan fingerprint mismatch) — "
                    "remove it or use a fresh spill_dir")
            for r in records:
                if r.get("t") != "block":
                    continue
                run_path = scratch.path(r["run"])
                if not os.path.exists(run_path):
                    continue  # spill never became durable: rebuild it
                mm = verify_spilled_run(run_path, r["run_crc"],
                                        f"spilled run {r['run']}")
                resumed[int(r["i"])] = (mm, r)
        jr = BuildJournal(jpath).open()
        scratch._journal = jr  # the lifecycle wrapper closes it on exit
        if not records:
            jr.append({"t": "begin", "v": BuildJournal.VERSION, "fp": fp_rec})

    store = CorpusStore(
        None, cfg, backend=backend,
        request_capacity=min(sb.request_capacity, plan.capacity_records),
    )
    frontier = None
    if streaming:
        budget = (sb.cache_budget_bytes if sb.cache_budget_bytes > 0
                  else DEFAULT_CACHE_BUDGET)
        # LRU half + read-ahead eighth + pool eighth; the rest is slack for
        # tie-depth chains and partition binary-search probes (probes release
        # per search, everything cached releases per partition).
        wb = store.k * 4
        frontier = _MergeFrontier(
            readahead_bytes=max(budget // 8, 2 * plan.num_superblocks * wb),
            window_bytes=wb,
            max_pool_windows=max(4, min(64, (budget // 8) // wb)),
        )

    def keep_run(sa_b: np.ndarray) -> np.ndarray:
        """Streaming: spill a sorted run, hand back its disk-backed memmap.
        Runs that are already spill memmaps (or views of one — e.g. the
        final text block, which the risk split passes through unfiltered)
        stay as they are: re-spilling would read the whole run back in."""
        if (scratch is not None and (streaming or jr is not None) and sa_b.size
                and not isinstance(sa_b, np.memmap)):
            return scratch.spill_run(sa_b)
        return sa_b

    # ---- phase 2: local SA per superblock (existing pipeline, one block
    # of items staged host-side + one block of records resident per run) --
    corpus_tokens = backend.n * max(1, backend.row_len)
    local_sas: List[np.ndarray] = []
    fp = Footprint(
        input=corpus_tokens * store.token_bytes,
        store_put=corpus_tokens * store.token_bytes,
        superblocks=plan.num_superblocks,
    )
    block_stats = []
    blocks = list(plan.blocks)
    # -- staging prefetch: while block i runs on device, the worker stages
    # block i+1 (up to pipeline_depth ahead).  On streaming builds each
    # prefetched block's bytes are registered through add_frontier so the
    # residency bound still holds with the read-ahead buffer resident — the
    # budget's non-LRU half (idle during phase 2) is the read-ahead ceiling,
    # and a block too big for it silently stages synchronously instead.
    stage_share = 0
    if streaming:
        budget = (sb.cache_budget_bytes if sb.cache_budget_bytes > 0
                  else DEFAULT_CACHE_BUDGET)
        stage_share = budget // 2
    prefetched: dict = {}
    pf_registered = 0

    def _submit_stages(next_i: int) -> None:
        nonlocal pf_registered
        if pipe is None:
            return
        for j in range(next_i, min(len(blocks), next_i + pipe.depth)):
            if j in prefetched or j in resumed:
                continue
            blo, bhi = blocks[j]
            reg = 0
            if streaming:
                reg = (bhi - blo) * max(1, backend.row_len) * 4
                if pf_registered + reg > stage_share:
                    break  # would overrun the budget share: stage it sync
                store.add_frontier(reg)
                pf_registered += reg
            # the worker runs the unaccounted read half; staged_items/bytes
            # are recorded on the main thread when the task is collected
            # below (note_staged at the hand-off — salint SAL010)
            prefetched[j] = (pipe.submit(store.stage_read, blo, bhi), reg)

    # journal records wait here until their run's async spill write is
    # observed complete on the main thread (SAL008: the journal itself is
    # touched only from here) — a journaled run must be durable before the
    # record promising it exists.
    pending_journal: List[tuple] = []

    def _flush_journal(force: bool = False) -> None:
        while pending_journal:
            rec, task = pending_journal[0]
            if task is not None:
                if not force and not task.done():
                    return
                task.result()  # re-raises a failed spill write
            jr.append(rec)  # fsync'd: the unit of recovery
            pending_journal.pop(0)

    # phase seconds, accumulated by the phases' spans
    timers = dict.fromkeys(_TIMERS, 0.0)
    for i, (lo, hi) in enumerate(blocks):
        pre = resumed.get(i)
        if pre is not None:
            # verified-complete on a prior attempt: adopt the journaled run,
            # stats, and footprint contributions without touching the store.
            mm, rec = pre
            local_sas.append(mm)
            block_stats.append(rec["stats"])
            bfc = rec.get("fpc", {})
            fp.shuffle += bfc.get("shuffle", 0)
            fp.fetch_request += bfc.get("fetch_request", 0)
            fp.fetch_response += bfc.get("fetch_response", 0)
            fp.rounds = max(fp.rounds, bfc.get("rounds", 0))
            fp.dropped += bfc.get("dropped", 0)
            fp.peak_records = max(fp.peak_records,
                                  rec["stats"]["num_suffixes"])
            journal_hits += 1
            continue
        with span("superblock.stage", timers, "t_stage_s"):
            entry = prefetched.pop(i, None)
            if entry is not None:
                task, reg = entry
                pipeline_point("stage:collect")
                block = task.result()  # staged in the background, not cached
                store.note_staged(lo, hi, block.nbytes)
                if reg:
                    store.add_frontier(-reg)
                    pf_registered -= reg
            else:
                block = store.stage_items(lo, hi)  # transient staging, not cached
            _submit_stages(i + 1)  # overlap: next blocks stage while this builds
        with span("superblock.block", timers, "t_build_s"):
            pipeline_point("build:block")
            if plan.text_mode:
                res = build_suffix_array(block, cfg=cfg, mesh=mesh)
                sa_b = res.suffix_array + lo
            else:
                lens_b = None if lengths is None else np.asarray(lengths)[lo:hi]
                res = build_suffix_array(block, lengths=lens_b, cfg=cfg, mesh=mesh)
                sa_b = res.suffix_array + (np.int64(lo) << plan.stride_bits)
            run = keep_run(sa_b)
            local_sas.append(run)
            bf = res.footprint
            fp.shuffle += bf.shuffle
            fp.fetch_request += bf.fetch_request
            fp.fetch_response += bf.fetch_response
            fp.rounds = max(fp.rounds, bf.rounds)
            fp.dropped += bf.dropped
            fp.peak_records = max(fp.peak_records, res.stats["num_suffixes"])
            block_stats.append(res.stats)
            timers["t_device_s"] += res.stats["t_device_s"]
            if jr is not None and isinstance(run, np.memmap):
                path, task = scratch.last_spill
                rec = {
                    "t": "block", "i": i,
                    "run": os.path.basename(path),
                    "run_crc": crc32_array(sa_b),
                    "rows": int(sa_b.size),
                    "stats": res.stats,
                    "fpc": {
                        "shuffle": int(bf.shuffle),
                        "fetch_request": int(bf.fetch_request),
                        "fetch_response": int(bf.fetch_response),
                        "rounds": int(bf.rounds),
                        "dropped": int(bf.dropped),
                    },
                }
                pending_journal.append((rec, task))
                _flush_journal()
    with span("superblock.drain"):
        if scratch is not None:
            scratch.drain_spills()  # spilled runs must be on disk before reads
        if jr is not None:
            _flush_journal(force=True)  # every run is durable now

    # ---- phase 3: boundary-exact merge via the store -------------------
    pre_requests = store.requests
    pre_fetch_s = store.timers["t_fetch_s"]
    with span("merge", timers, "t_merge_s"):
        sa, sink, refiner, cur, peak_candidates = _merge_phase(
            store, backend, cfg, sb, plan, local_sas, block_stats,
            original_corpus=original_corpus, lengths=lengths, mesh=mesh,
            streaming=streaming, keep_run=keep_run, scratch=scratch,
            frontier=frontier, jr=jr, pipe=pipe, sinks=sinks, timers=timers)
    timers["t_fetch_s"] = store.timers["t_fetch_s"] - pre_fetch_s
    if sanitize_enabled(sb):
        check_footprint(store, backend)

    dev_req = refiner.requests if refiner else 0
    dev_req_bytes = refiner.request_bytes if refiner else 0
    dev_resp_bytes = refiner.response_bytes if refiner else 0
    fp.fetch_request += store.request_bytes + dev_req_bytes
    fp.fetch_response += store.response_bytes + dev_resp_bytes
    fp.output = int(sa.shape[0]) * 8
    fp.peak_records = max(fp.peak_records, store.peak_windows,
                          refiner.peak_records if refiner else 0,
                          peak_candidates, sink.max_piece)
    fp.materialized = fp.peak_records * 16
    fp.peak_resident_bytes = store.peak_resident_bytes

    stats = {
        "num_suffixes": int(sa.shape[0]),
        "emitted": int(sa.shape[0]),
        "superblocks": plan.num_superblocks,
        "capacity_records": plan.capacity_records,
        "peak_records": fp.peak_records,
        "merge_algorithm": sb.merge_algorithm,
        "merge_backend": sb.merge_backend,
        "merge_pieces": sink.pieces,
        "max_piece": int(sink.max_piece),
        "merge_fetch_requests": int(store.requests - pre_requests) + dev_req,
        # store + device-refiner counters are merge-only (neither serves any
        # phase-2 fetch)
        "merge_fetch_bytes": int(
            store.request_bytes + store.response_bytes
            + dev_req_bytes + dev_resp_bytes
        ),
        "merge_fetch_rounds": int(store.rounds)
        + (refiner.rounds if refiner else 0),
        "merge_retries": int(store.retries),
        "merge_cursor_peak_windows": cur.peak_cached_windows,
        "block_rounds": [s["rounds"] for s in block_stats],
        "dropped": fp.dropped,
        "unresolved": sum(s["unresolved"] for s in block_stats),
        # store-backend residency
        "store_backend": "chunked" if streaming else "memory",
        "corpus_bytes": backend.corpus_bytes,
        "peak_resident_bytes": fp.peak_resident_bytes,
        "store_cache_hits": backend.cache_hits,
        "store_cache_misses": backend.cache_misses,
        "store_cache_hit_rate": backend.hit_rate,
        "spilled_runs": scratch.spilled_runs if scratch else 0,
        "spilled_bytes": scratch.spilled_bytes if scratch else 0,
        "emit_lcp": bool(sb.emit_lcp),
        "sanitized": sanitize_enabled(sb),
        # crash-safety layer: journal replay + retrying store
        "journaled": jr is not None,
        "journal_hits": int(journal_hits),
        "store_retry_attempts": int(getattr(backend, "retry_attempts", 0)),
        "store_retried_calls": int(getattr(backend, "retried_calls", 0)),
        "pipeline_depth": int(sb.pipeline_depth),
        # phase seconds: what each overlap in the pipelined schedule can
        # hide behind (staging behind t_build_s, refill gathers inside
        # t_merge_s), and the merge's own phases inside t_merge_s
        **timers,
    }
    res = SAResult(suffix_array=sa, footprint=fp, stats=stats, lcp=sink.lcp)
    if sb.write_manifest:
        _write_index_manifest(res, backend, cfg, sb, scratch)
    if jr is not None:
        # terminal record, then retire the journal: the build is complete
        # and the index artifacts are published — nothing left to resume.
        jr.append({"t": "done", "rows": int(sa.shape[0])})
        jr.finalize()
    return res


def _merge_phase(store, backend, cfg, sb, plan, local_sas, block_stats, *,
                 original_corpus, lengths, mesh, streaming, keep_run, scratch,
                 frontier, jr, pipe, sinks, timers):
    """Phase 3 of :func:`_build_superblock_phases`: merge the blocks' sorted
    runs into the final order through the store.  Returns ``(sa, sink,
    refiner, cursor, peak_candidates)``; the merge's phases add their
    seconds to ``timers``."""
    samples = max(1, min(
        sb.samples_per_block,
        plan.capacity_records // plan.num_superblocks,
    ))
    cap = plan.capacity_records
    total_suffixes = int(sum(r.size for r in local_sas))
    out_path = (os.path.join(sb.spill_dir, "suffix_array.npy")
                if sb.spill_dir is not None else None)
    pair_lcp = None
    lcp_path = None
    if sb.emit_lcp:
        # emit order is final order: each emitted suffix's LCP is one
        # adjacent compare against the previously emitted one, served by the
        # same store the merge streams through (repro.core.lcp).
        def pair_lcp(a, b):
            return pairwise_lcp(store, a, b)

        if sb.spill_dir is not None:
            lcp_path = os.path.join(sb.spill_dir, "lcp.npy")
    sink = _OutputSink(total_suffixes, memmap_path=out_path,
                       lcp_path=lcp_path, pair_lcp=pair_lcp, executor=pipe)
    sinks.append(sink)
    if jr is not None:
        sink = _JournalingSink(sink, jr)  # emitted-rows watermark records
    if sanitize_enabled(sb):
        # order-verify emitted pieces through a private audit store: the
        # build store's traffic counters (gated by tests) stay clean.
        sink = SanitizingSink(sink, backend, cfg,
                              request_capacity=sb.request_capacity)
    peak_candidates = 0

    cur = WindowCursor(store)
    refiner: Optional[DeviceRefiner] = None
    if sb.merge_backend == "device":
        refiner = DeviceRefiner(
            original_corpus if isinstance(original_corpus, np.ndarray)
            else store.stage_items(0, backend.n),
            cfg, lengths=lengths, mesh=mesh,
        )
        refine = refiner.refine
    else:
        # kway: warm the merge cursor with every re-rank fetch so the k-way
        # phase re-serves those windows instead of re-fetching them.  Not in
        # streaming mode: warming would keep one window per re-ranked suffix
        # resident, unbounding the frontier — the read-ahead re-fetches what
        # it actually needs instead.
        warm = cur if (sb.merge_algorithm == "kway" and not streaming) else None

        def refine(g: np.ndarray) -> np.ndarray:
            return _refine_sort(store, g, cursor=warm)

    def _risk_free_runs() -> Tuple[List[np.ndarray], List[np.ndarray]]:
        """The exactly-sorted runs of the merge, as ``(runs, risk_pieces)``:
        block SAs with text-mode boundary-risk suffixes (and unproven blocks)
        re-ranked into extra sorted pieces that join the merge as runs of
        their own.  ``runs`` empty means every suffix was at risk — the
        re-ranked pieces are then consecutive intervals of the true order
        and need no merge at all."""
        if plan.text_mode:
            runs, risk = _split_boundary_risk(
                plan, local_sas, block_stats, store.k
            )
            runs = [keep_run(r) for r in runs]  # re-spill the filtered runs
            risk_pieces: List[np.ndarray] = []
            if risk.size:
                risk_pieces = [
                    keep_run(p)
                    for p in _sorted_runs(store, risk, cap, samples, refine)
                    if p.size
                ]
            if scratch is not None:
                scratch.drain_spills()  # the merge reads these runs next
            return runs, risk_pieces
        # reads mode: block runs are exact as-is (suffixes never cross a
        # read) — unless a block hit the refinement hard cap, in which
        # case its order is unproven and it is re-ranked like a risk set.
        runs, bad = [], []
        for sa_b, st in zip(local_sas, block_stats, strict=True):
            (runs if st.get("unresolved", 0) == 0 else bad).append(sa_b)
        pieces = []
        if bad:
            pieces = [
                keep_run(p) for p in _sorted_runs(
                    store, np.concatenate(bad), cap, samples, refine)
                if p.size
            ]
        if scratch is not None:
            scratch.drain_spills()
        return runs, pieces

    if sb.merge_algorithm == "rerank":
        # PR-1 baseline: every bucket re-ranked from scratch (block order is
        # only used for splitter sampling).  Kept as the traffic reference.
        for p in _sorted_runs(store, np.concatenate(local_sas), cap, samples,
                              refine):
            sink.append(p)
    elif sb.merge_algorithm == "merge_path":
        # tentpole path: no splitter partition, no heap — the runs are
        # merged directly by batched merge-path tiles (text-mode risk sets
        # are re-ranked first exactly as in the k-way path).
        runs, risk_pieces = _risk_free_runs()
        if runs:
            peak_candidates = _merge_path_runs(
                store, runs + risk_pieces, sink, cap, sb.merge_tile,
                cfg.use_pallas, refiner=refiner, frontier=frontier,
                executor=pipe, timers=timers,
            )
        else:
            # every suffix was at risk: the re-ranked pieces already are
            # consecutive intervals of the true order — no merge needed.
            for p in risk_pieces:
                sink.append(p)
    else:
        # Splitter pools are lists of already-sorted pick runs: cursor-merge
        # them so their windows are fetched once and stay hot for the
        # partition probes and bucket merges (cheaper than any re-rank, on
        # either backend — the device refiner serves the true re-rank
        # workloads: text-mode risk sets and the rerank algorithm).
        def rank_pool(pool_runs: List[np.ndarray]) -> np.ndarray:
            return _kway_merge(cur, pool_runs, release=False)

        runs, risk_pieces = _risk_free_runs()
        if runs:
            for p in _merge_runs(cur, runs + risk_pieces, cap, samples,
                                 rank_pool, frontier=frontier):
                sink.append(p)
        else:
            for p in risk_pieces:
                sink.append(p)
    return sink.result(), sink, refiner, cur, peak_candidates


def _write_index_manifest(
    res: SAResult,
    backend: StoreBackend,
    cfg: SAConfig,
    sb: SuperblockConfig,
    scratch: Optional[_Scratch],
) -> None:
    """Finalize ``sb.spill_dir`` as a reopenable index directory.

    The corpus is referenced in place when the backend serves a persistent
    chunked file (the caller's own corpus file, or the copy
    ``_resolve_backend`` already placed in ``spill_dir``); a scratch-resident
    or in-memory corpus is serialized into the directory, since scratch dies
    with the build.
    """
    from repro.core import index_io

    corpus_ref = None
    p = getattr(backend, "path", None)
    if p is not None:
        ap = os.path.abspath(p)
        in_scratch = scratch is not None and ap.startswith(
            os.path.abspath(scratch.dir) + os.sep)
        if not in_scratch:
            corpus_ref = ap
    index_io.save_index(
        sb.spill_dir, cfg, backend, res.suffix_array, res.lcp, res.stats,
        corpus_ref=corpus_ref, chunk_items=sb.chunk_records,
        timers=res.stats,
    )
    res.stats["index_dir"] = sb.spill_dir


def build_suffix_array_auto(
    corpus,
    lengths=None,
    cfg: SAConfig = SAConfig(),
    sb: Optional[SuperblockConfig] = None,
    mesh=None,
) -> SAResult:
    """Single entry point: single-pass when the record set fits one run,
    out-of-core superblocks when it does not (the launcher's policy).
    Accepts the same corpus forms as :func:`build_suffix_array_superblock`
    (array / chunked file path / store backend)."""
    sb = sb or SuperblockConfig()
    plan = plan_superblocks(corpus_shape_of(corpus), cfg, sb)
    if (plan.num_superblocks <= 1
            and not (sb.emit_lcp or sb.write_manifest)):
        if not isinstance(corpus, np.ndarray):
            corpus = _materialize_corpus(corpus, cfg)
        return build_suffix_array(corpus, lengths=lengths, cfg=cfg, mesh=mesh)
    # index finalization (LCP / manifest) always runs through the superblock
    # wrapper: its single-block early path owns the LCP + save.
    return build_suffix_array_superblock(
        corpus, lengths=lengths, cfg=cfg, sb=sb, mesh=mesh
    )


def _materialize_corpus(corpus, cfg: SAConfig) -> np.ndarray:
    """Whole-corpus host materialization for the single-pass fallback (a
    plan that fits one run is in-core by definition)."""
    if isinstance(corpus, StoreBackend):
        return np.asarray(materialize_backend(corpus), np.int32)
    if isinstance(corpus, (str, os.PathLike)):
        from repro.data import chunk_store

        return chunk_store.load_corpus(os.fspath(corpus))
    return np.asarray(corpus, np.int32)
