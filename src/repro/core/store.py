"""ShardedStore — the distributed in-memory data store (paper §IV, Redis).

"Keeping only the raw data in place": the corpus lives sharded across device
HBM; everything else communicates *indexes*.  ``mget_window`` is the TPU-native
``mgetsuffix`` (the paper's custom batched Redis command): an aggregated batch
of suffix indexes is routed to owner devices with one all_to_all, owners gather
the K-token windows from their resident shard, and a second all_to_all returns
the windows (or — beyond-paper ``server_pack`` — the already-packed key words,
halving response bytes the same way mgetsuffix halves them vs whole reads).

Placement: the paper places read ``seq mod n``; we place contiguous row blocks
(``owner = row // rows_per_shard``) which is the same O(1) arithmetic but keeps
halo windows local in long-text mode (DESIGN.md §2).

All methods are *per-device* functions meant to be called inside ``shard_map``.
"""
from __future__ import annotations

import functools
import math
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Iterator, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.config import SAConfig
from repro.core import encoding
from repro.core.distributed import bucket_scatter, exchange
from repro.core.integrity import (
    CorruptionError,
    DEFAULT_RETRYABLE,
    TransientStoreError,
)
from repro.core.trace import span
from repro.core.types import WORD_BITS, KEY_SENTINEL


# Default resident-byte budget of the chunked store backend (LRU chunk
# cache + merge frontier share it; see superblock._build_superblock).
DEFAULT_CACHE_BUDGET = 64 << 20


def index_request_bytes(num_items: int, stride_bits: int) -> int:
    """Modeled bytes of one suffix-index request.

    An index addresses ``(item, offset)`` packed as ``item << stride | off``
    (``repro.core.types``) and needs int31 words carried in int32 lanes —
    one word while the address space fits 31 bits, two beyond.  The paper
    ships a fixed 8-byte long; deriving the width from the store geometry
    keeps request-byte accounting exact for small stores and for both index
    packings (single-word text positions vs two-word read/offset pairs).
    This is the *effective* figure: the device path's padded all_to_all
    still physically carries two int32 lanes per slot, reported separately
    in ``FetchStats.padded_request_bytes``.
    """
    bits = max(1, (max(num_items - 1, 1)).bit_length() + stride_bits)
    return 4 * -(-bits // WORD_BITS)


@dataclass(frozen=True)
class StoreSpec:
    """Static layout of the sharded store."""

    axis: str
    num_shards: int
    rows_per_shard: int  # reads mode: rows; text mode: tokens
    row_len: int  # L (reads) or 1 (text)
    request_capacity: int  # per-destination all_to_all capacity

    @property
    def is_text(self) -> bool:
        return self.row_len == 1

    @property
    def index_bytes(self) -> int:
        """Modeled per-request index bytes, derived — not a hard-coded 8 B
        (see :func:`index_request_bytes`)."""
        stride = 0 if self.is_text else int(math.ceil(math.log2(self.row_len + 1)))
        return index_request_bytes(self.num_shards * self.rows_per_shard, stride)


@dataclass
class FetchStats:
    """Per-call effective/padded byte counters (jnp scalars)."""

    requests: jnp.ndarray
    request_bytes: jnp.ndarray
    response_bytes: jnp.ndarray
    padded_request_bytes: int
    padded_response_bytes: int
    dropped: jnp.ndarray


def token_bytes(vocab_size: int) -> int:
    """Bytes per raw token for footprint accounting (paper counts chars)."""
    return max(1, (max(vocab_size, 1).bit_length() + 7) // 8)


def pack_keys_np(windows: np.ndarray, cfg: SAConfig) -> np.ndarray:
    """Numpy mirror of :func:`repro.core.encoding.pack_words`.

    (..., K) int32 token windows -> (..., key_words) int32 packed key words
    whose row-lexicographic order equals the token-window order (the same
    order-preserving packing as the Map-phase ``prefix_pack`` kernel).  This
    is the single compare representation of the out-of-core merge: the
    merge-path kernel ranks these words, and the splitter binary search
    (:class:`WindowCursor`) caches and compares them.
    """
    w = np.asarray(windows, np.int64)
    cpw = cfg.resolved_chars_per_word()
    n_words = cfg.key_words
    assert w.shape[-1] == cpw * n_words, (w.shape, cpw * n_words)
    out = np.empty(w.shape[:-1] + (n_words,), np.int32)
    if cfg.packing == "base":
        base = cfg.vocab_size + 1
        for i in range(n_words):
            acc = np.zeros(w.shape[:-1], np.int64)
            for j in range(i * cpw, (i + 1) * cpw):
                acc = acc * base + w[..., j]
            out[..., i] = acc.astype(np.int32)
    else:
        bits = max(1, int(cfg.vocab_size).bit_length())
        for i in range(n_words):
            acc = np.zeros(w.shape[:-1], np.int64)
            for j in range(i * cpw, (i + 1) * cpw):
                acc = (acc << bits) | w[..., j]
            out[..., i] = (acc << (31 - bits * cpw)).astype(np.int32)
    return out


def lex_less_rows(a: np.ndarray, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Row-wise lexicographic compare of two (m, W) key-word matrices.

    Returns ``(less, equal)`` bool vectors — the vectorized comparator shared
    by the cursor's progressive suffix compare and the merge-path driver.
    """
    lt = np.zeros(a.shape[0], bool)
    eq = np.ones(a.shape[0], bool)
    for w in range(a.shape[1]):
        lt |= eq & (a[:, w] < b[:, w])
        eq &= a[:, w] == b[:, w]
    return lt, eq


def mget_window(
    local_rows: jnp.ndarray,
    row_id: jnp.ndarray,
    offset: jnp.ndarray,
    active: jnp.ndarray,
    spec: StoreSpec,
    cfg: SAConfig,
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, FetchStats]:
    """Batched remote window fetch ("mgetsuffix").

    Args:
      local_rows: this device's resident shard, (rows_per_shard, L) int32
        (text mode: (rows_per_shard,) tokens, treated as rows of length 1 —
        windows then span following rows via the flattened layout).
      row_id/offset: (M,) int32 *global* row ids and offsets to fetch.
      active: (M,) bool — inactive slots are not routed (zero windows).
      window: tokens per window (default cfg.prefix_len).
    Returns:
      (win_or_words, exhausted, ok, stats):
        win_or_words: word-major (K, M) raw token windows, or
          (key_words, M) packed words when cfg.server_pack (beyond-paper
          response compression);
        exhausted: (M,) bool — the window ran past the end of the suffix;
        ok: (M,) bool — request was actually served (False = capacity drop,
          the caller must retry; see pipeline group-synchronous retry).
    """
    k = window or cfg.prefix_len
    d, cap = spec.num_shards, spec.request_capacity

    owner = jnp.where(
        active, (row_id // spec.rows_per_shard).astype(jnp.int32), jnp.int32(d)
    )
    owner = jnp.clip(owner, 0, d)  # inactive -> dump bucket d (dropped slot)
    reqs = jnp.stack(
        [jnp.where(active, row_id, -1), jnp.where(active, offset, 0)]
    )
    # bucket over d+1 buckets; bucket d is a local dump that is never sent.
    buf, slot, _ = bucket_scatter(reqs, owner, d + 1, cap, fill=-1)
    # true overflow drops: active requests that landed past their bucket cap
    dropped = jnp.sum(active & (slot >= d * cap)).astype(jnp.int32)

    recv = exchange(buf[:, :d], spec.axis)  # (2, d, cap) requests per device
    req_row = recv[0].reshape(-1)
    req_off = recv[1].reshape(-1)
    base = lax.axis_index(spec.axis) * spec.rows_per_shard
    local_row = jnp.where(req_row >= 0, req_row - base, -1)

    if spec.is_text:
        toks = _text_window(local_rows.reshape(-1), local_row, req_off, k)
    elif cfg.use_pallas:
        from repro.kernels import ops as kops  # Pallas mgetsuffix gather

        toks = kops.window_gather(local_rows, local_row, req_off, k).T
    else:
        toks = encoding.window_columns(local_rows, local_row, req_off, k)
    # suffix ends inside this window  =>  contains padding zeros
    exhausted_w = functools.reduce(jnp.logical_or, [t == 0 for t in toks])

    if cfg.server_pack:
        body = encoding.pack_columns(toks, cfg)  # (key_words, d*cap)
        per_resp_bytes = 4 * cfg.key_words
    else:  # paper-faithful: ship the raw window tokens
        body = jnp.stack(list(toks))  # (k, d*cap)
        per_resp_bytes = k * token_bytes(cfg.vocab_size)
    resp_width = body.shape[0]
    payload = jnp.concatenate([body, exhausted_w[None].astype(jnp.int32)])

    resp = exchange(payload.reshape(resp_width + 1, d, cap), spec.axis)
    flatresp = resp.reshape(resp_width + 1, d * cap)
    # route responses back to the original request slots
    guard = jnp.zeros((resp_width + 1, 1), flatresp.dtype)
    flatresp = jnp.concatenate([flatresp, guard], axis=1)
    slot_c = jnp.clip(slot, 0, d * cap)
    back = flatresp[:, slot_c]
    ok = active & (slot < d * cap)
    out = jnp.where(ok[None, :], back[:resp_width], 0)
    exhausted = jnp.where(ok, back[resp_width] > 0, True)

    n_ok = jnp.sum(ok).astype(jnp.int32)
    # request_bytes: the modeled mgetsuffix index width (spec.index_bytes,
    # derived from the address space; the paper ships one 8-byte long).
    # padded_request_bytes: the physical all_to_all capacity — every slot
    # carries 2 int32 lanes regardless of how few bits the index needs.
    stats = FetchStats(
        requests=n_ok,
        request_bytes=n_ok * spec.index_bytes,
        response_bytes=n_ok * per_resp_bytes,
        padded_request_bytes=d * cap * 8,
        padded_response_bytes=d * cap * per_resp_bytes,
        dropped=dropped,
    )
    return out, exhausted, ok, stats


def _text_window(flat: jnp.ndarray, local_pos: jnp.ndarray, off: jnp.ndarray,
                 k: int) -> list:
    """Text-mode window gather from a flat local token shard (0-padded), as
    k token columns (see :func:`repro.core.encoding.window_columns`)."""
    n = flat.shape[0]
    padded = jnp.pad(flat, (0, k))
    pos = jnp.where(local_pos >= 0, local_pos + off, n)
    pos = jnp.clip(pos, 0, n)
    return [padded[jnp.clip(pos + j, 0, n + k - 1)] for j in range(k)]


def mget_scalar(
    local_vals: jnp.ndarray,
    pos: jnp.ndarray,
    active: jnp.ndarray,
    spec: StoreSpec,
    fill: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fetch one int32 per global position (the *rank store* used by the
    beyond-paper prefix-doubling variant — same store abstraction, values are
    Manber–Myers ranks instead of tokens).  Returns (values, dropped)."""
    d, cap = spec.num_shards, spec.request_capacity
    owner = jnp.where(
        active & (pos >= 0) & (pos < d * spec.rows_per_shard),
        (pos // spec.rows_per_shard).astype(jnp.int32),
        jnp.int32(d),
    )
    reqs = jnp.stack([pos, jnp.zeros_like(pos)])
    buf, slot, _ = bucket_scatter(reqs, owner, d + 1, cap, fill=-1)
    dropped = jnp.sum(active & (slot >= d * cap)).astype(jnp.int32)
    recv = exchange(buf[:, :d], spec.axis)
    req_pos = recv[0].reshape(-1)
    base = lax.axis_index(spec.axis) * spec.rows_per_shard
    lp = req_pos - base
    ok = (req_pos >= 0) & (lp >= 0) & (lp < spec.rows_per_shard)
    vals = jnp.where(ok, local_vals[jnp.clip(lp, 0, spec.rows_per_shard - 1)], fill)
    resp = exchange(vals.reshape(1, d, cap), spec.axis).reshape(-1)
    resp = jnp.concatenate([resp, jnp.array([fill], resp.dtype)])
    back = resp[jnp.clip(slot, 0, d * cap)]
    ok2 = active & (slot < d * cap)
    return jnp.where(ok2, back, fill), dropped


def scatter_update(
    local_vals: jnp.ndarray,
    pos: jnp.ndarray,
    values: jnp.ndarray,
    active: jnp.ndarray,
    spec: StoreSpec,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter (pos -> value) into the sharded store (rank write-back).

    Returns (new_local_vals, dropped)."""
    d, cap = spec.num_shards, spec.request_capacity
    owner = jnp.where(
        active & (pos >= 0) & (pos < d * spec.rows_per_shard),
        (pos // spec.rows_per_shard).astype(jnp.int32),
        jnp.int32(d),
    )
    reqs = jnp.stack([pos, values])
    buf, slot, _ = bucket_scatter(reqs, owner, d + 1, cap, fill=-1)
    dropped = jnp.sum(active & (slot >= d * cap)).astype(jnp.int32)
    recv = exchange(buf[:, :d], spec.axis).reshape(2, d * cap)
    base = lax.axis_index(spec.axis) * spec.rows_per_shard
    lp = recv[0] - base
    ok = (recv[0] >= 0) & (lp >= 0) & (lp < spec.rows_per_shard)
    lp_c = jnp.where(ok, lp, spec.rows_per_shard)
    padded = jnp.concatenate([local_vals, jnp.zeros((1,), local_vals.dtype)])
    padded = padded.at[lp_c].set(jnp.where(ok, recv[1], padded[lp_c]))
    return padded[: spec.rows_per_shard], dropped


# ---------------------------------------------------------------------------
# Store backends: where the corpus bytes actually live
# ---------------------------------------------------------------------------


class StoreBackend:
    """Protocol for the raw-token substrate behind :class:`CorpusStore`.

    A backend owns the corpus *bytes* and answers exact window gathers; the
    store on top owns capacity/retry semantics and traffic accounting.  Two
    residency regimes implement it:

    * :class:`InMemoryBackend` — the whole corpus host-resident (the PR-1/2
      behavior; ``resident_bytes`` == corpus bytes, constant);
    * :class:`ChunkedFileBackend` — corpus on disk in the chunked format
      (``repro.data.chunk_store``), an LRU chunk cache bounded by
      ``cache_budget_bytes`` the only resident copy.

    Shared geometry (set by :meth:`_init_geometry`): ``text_mode``, ``n``
    (items), ``row_len``, ``stride_bits``, ``max_len``, ``k``.  Subclasses
    implement :meth:`gather` (exact (m, K) windows for global suffix ids at a
    K-token depth) and :meth:`read_items` (materialize a contiguous item
    range — the superblock build's per-block staging, *not* cached).
    """

    def _init_geometry(self, text_mode: bool, items: int, row_len: int,
                       cfg: SAConfig) -> None:
        self.text_mode = text_mode
        self.n = items
        self.row_len = row_len
        self.k = cfg.prefix_len
        if text_mode:
            self.stride_bits = 0
            self.max_len = items
        else:
            self.stride_bits = int(math.ceil(math.log2(row_len + 1)))
            self.max_len = row_len + 1
        self.corpus_bytes = items * row_len * 4  # int32 lanes
        self.cache_hits = 0
        self.cache_misses = 0

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.n,) if self.text_mode else (self.n, self.row_len)

    @property
    def resident_bytes(self) -> int:
        raise NotImplementedError

    @property
    def hit_rate(self) -> float:
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 1.0

    def gather(self, gidx: np.ndarray, depth: np.ndarray) -> np.ndarray:
        """(m,) int64 global suffix ids -> (m, K) windows at token offset
        ``depth * K`` into each suffix (0-padded past the end)."""
        raise NotImplementedError

    def read_items(self, lo: int, hi: int) -> np.ndarray:
        raise NotImplementedError

    def close(self) -> None:  # optional hook, default no-op
        pass


class InMemoryBackend(StoreBackend):
    """Whole-corpus host-resident backend (the original CorpusStore body)."""

    def __init__(self, corpus, cfg: SAConfig):
        corpus = np.asarray(corpus, np.int32)
        text_mode = corpus.ndim == 1
        if text_mode:
            items, row_len = corpus.shape[0], 1
        else:
            items, row_len = corpus.shape
        self._init_geometry(text_mode, items, row_len, cfg)
        self._corpus = corpus
        if text_mode:
            self._flat = np.concatenate([corpus, np.zeros(self.k, np.int32)])
        else:
            self._rows = np.pad(corpus, ((0, 0), (0, self.k)))

    @property
    def resident_bytes(self) -> int:
        return int((self._flat if self.text_mode else self._rows).nbytes)

    def gather(self, gidx: np.ndarray, depth: np.ndarray) -> np.ndarray:
        self.cache_hits += int(gidx.shape[0])  # always resident
        if self.text_mode:
            pos = np.minimum(gidx + depth * self.k, self.n)
            cols = pos[:, None] + np.arange(self.k)[None, :]
            return self._flat[np.minimum(cols, self.n + self.k - 1)]
        row = (gidx >> self.stride_bits).astype(np.int64)
        off = (gidx & ((1 << self.stride_bits) - 1)).astype(np.int64)
        off = np.minimum(off + depth * self.k, self.max_len - 1)
        cols = off[:, None] + np.arange(self.k)[None, :]
        return self._rows[row[:, None], cols]

    def read_items(self, lo: int, hi: int) -> np.ndarray:
        return self._corpus[lo:hi]


class ChunkedFileBackend(StoreBackend):
    """Disk-resident backend: chunked corpus file + budgeted LRU chunk cache.

    The corpus lives in the ``repro.data.chunk_store`` on-disk format and
    only cached chunks are host-resident: ``resident_bytes`` is the exact sum
    of cached chunk array bytes and never exceeds ``cache_budget_bytes``
    (eviction runs *before* a miss loads, so the bound holds at every
    instant).  Text-mode chunks carry a K-token halo so windows straddling a
    chunk edge are served from one chunk exactly; reads-mode rows are atomic
    within a chunk by construction.  ``read_items`` streams straight from
    the file (pread) without touching the cache — per-superblock staging is
    transient and must not evict the merge's working set.

    One ``gather`` serves the chunks resident at its start first, then loads
    the missing ones, each in ascending id (so preads stay in file order).
    Each missing chunk's rows are copied out before the next one loads, so
    eviction only ever takes chunks the call does not need or has already
    served: a call misses exactly the chunks it needs that were not resident
    when it began.  (An ascending sweep over more chunks than the cache
    holds would evict, as least recent, the resident chunks it reaches
    later.)  ``reordered_gathers`` counts the calls whose order differed
    from ascending.
    """

    def __init__(self, path: str, cfg: SAConfig, cache_budget_bytes: int = 0,
                 verify: bool = True):
        from repro.data.chunk_store import ChunkedCorpusReader

        # every chunk the LRU caches is crc-checked on load (v2 files);
        # crc32 is cheap next to the read, so verification defaults on.
        self._reader = ChunkedCorpusReader(path, verify=verify)
        meta = self._reader.meta
        self._init_geometry(meta.text_mode, meta.items, meta.row_len, cfg)
        self.path = path
        self.chunk_items = meta.chunk_items
        self.num_chunks = meta.num_chunks
        # a text chunk resident in cache carries its K-token halo
        halo_bytes = self.k * 4 if meta.text_mode else 0
        self._full_chunk_bytes = meta.chunk_bytes + halo_bytes
        if cache_budget_bytes <= 0:
            cache_budget_bytes = DEFAULT_CACHE_BUDGET
        if cache_budget_bytes < self._full_chunk_bytes:
            self._reader.close()  # constructor raises: don't leak the fd
            raise ValueError(
                f"chunk cache budget of {cache_budget_bytes} B cannot hold "
                f"one chunk ({self._full_chunk_bytes} B). The streaming "
                "build gives the LRU half of SuperblockConfig."
                "cache_budget_bytes — lower chunk_records (or rewrite the "
                "corpus file with smaller chunks), or raise the budget"
            )
        self.cache_budget_bytes = int(cache_budget_bytes)
        self._cache: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self._resident = 0
        self.evictions = 0
        self.reordered_gathers = 0
        # seconds in the miss path (eviction + read + crc check)
        self.timers = {"t_chunk_read_s": 0.0}

    @property
    def resident_bytes(self) -> int:
        return self._resident

    def close(self) -> None:
        self._cache.clear()
        self._resident = 0
        self._reader.close()

    def _chunk(self, ci: int) -> np.ndarray:
        chunk = self._cache.get(ci)
        if chunk is not None:
            self._cache.move_to_end(ci)
            self.cache_hits += 1
            return chunk
        self.cache_misses += 1
        with span("store.chunk_read", self.timers, "t_chunk_read_s"):
            incoming = self._full_chunk_bytes  # upper bound (tail chunks shorter)
            while self._cache and self._resident + incoming > self.cache_budget_bytes:
                _, old = self._cache.popitem(last=False)
                self._resident -= old.nbytes
                self.evictions += 1
            chunk = self._reader.read_chunk(
                ci, halo=self.k if self.text_mode else 0)
        self._cache[ci] = chunk
        self._resident += chunk.nbytes
        return chunk

    def gather(self, gidx: np.ndarray, depth: np.ndarray) -> np.ndarray:
        gidx = np.asarray(gidx, np.int64)
        m = gidx.shape[0]
        depth = np.broadcast_to(np.asarray(depth, np.int64), (m,))
        out = np.zeros((m, self.k), np.int32)
        if self.text_mode:
            pos = np.minimum(gidx + depth * self.k, self.n)
            ci = np.minimum(pos // self.chunk_items, self.num_chunks - 1)
        else:
            row = (gidx >> self.stride_bits).astype(np.int64)
            off = (gidx & ((1 << self.stride_bits) - 1)).astype(np.int64)
            off = np.minimum(off + depth * self.k, self.max_len - 1)
            ci = row // self.chunk_items
        chunks = np.unique(ci).tolist()
        order = ([c for c in chunks if c in self._cache]
                 + [c for c in chunks if c not in self._cache])
        if order != chunks:
            self.reordered_gathers += 1
        for c in order:
            sel = np.flatnonzero(ci == c)
            chunk = self._chunk(c)
            base = c * self.chunk_items
            if self.text_mode:
                local = pos[sel] - base  # halo covers the straddle/tail
                cols = local[:, None] + np.arange(self.k)[None, :]
                out[sel] = chunk[cols]
            else:
                cols = off[sel][:, None] + np.arange(self.k)[None, :]
                valid = cols < self.row_len  # zero-pad past the row end
                cc = np.minimum(cols, self.row_len - 1)
                out[sel] = np.where(valid, chunk[row[sel] - base][
                    np.arange(sel.size)[:, None], cc], 0)
        return out

    def read_items(self, lo: int, hi: int) -> np.ndarray:
        return self._reader.read_items(lo, hi)


class ThrottledBackend(StoreBackend):
    """Deterministic slow-medium proxy around any :class:`StoreBackend`.

    Adds a fixed ``time.sleep`` to every ``gather`` and ``read_items`` call,
    simulating the paper's network/disk tier with a latency that does not
    depend on machine load: the sleep releases the GIL, so a pipelined build
    over it overlaps genuinely, and its speedup against a synchronous build
    is a property of the schedule, not of the host's momentary disk speed.
    Geometry and counters delegate to the wrapped backend; accounting
    semantics are unchanged.
    """

    def __init__(self, inner: StoreBackend, gather_delay_s: float = 0.0,
                 read_delay_s: float = 0.0):
        self.inner = inner
        self.gather_delay_s = float(gather_delay_s)
        self.read_delay_s = float(read_delay_s)
        self.gather_calls = 0
        self.read_calls = 0
        self.throttled_calls = 0
        self.throttled_sleep_s = 0.0

    def __getattr__(self, name: str):
        return getattr(self.inner, name)

    @property
    def resident_bytes(self) -> int:
        return self.inner.resident_bytes

    def _sleep(self, seconds: float) -> None:
        if seconds > 0:
            time.sleep(seconds)
            self.throttled_calls += 1
            self.throttled_sleep_s += seconds

    def gather(self, gidx: np.ndarray, depth: np.ndarray) -> np.ndarray:
        self.gather_calls += 1
        self._sleep(self.gather_delay_s)
        return self.inner.gather(gidx, depth)

    def read_items(self, lo: int, hi: int) -> np.ndarray:
        self.read_calls += 1
        self._sleep(self.read_delay_s)
        return self.inner.read_items(lo, hi)

    def close(self) -> None:
        self.inner.close()


class RetryingBackend(StoreBackend):
    """Transparent retry proxy around any :class:`StoreBackend`.

    Backend reads/gathers that raise a *transient* error (the
    ``retryable`` allowlist, by default the shared
    :data:`~repro.core.integrity.DEFAULT_RETRYABLE` taxonomy) are retried
    with deterministic capped exponential backoff — no jitter, so a retried
    build is reproducible.  :class:`~repro.core.integrity.CorruptionError`
    is **never** retried: corrupt bytes stay corrupt, and masking them
    behind a retry loop would turn a detectable fault into a wrong answer.

    Retry accounting lives in ``retry_attempts`` / ``retried_calls`` /
    ``gave_up`` — deliberately *not* the gated :class:`FetchStats` counter
    names (salint SAL010): the store's traffic counters are a property of
    the access schedule, and a flaky medium must not change what the
    traffic-equality benchmark gates measure.  ``sleep`` is injectable so
    tests assert the backoff sequence without wall-clock cost.
    """

    def __init__(self, inner: StoreBackend, retries: int = 3,
                 backoff_s: float = 0.01, max_backoff_s: float = 1.0,
                 retryable=DEFAULT_RETRYABLE, sleep=time.sleep):
        self.inner = inner
        self.retries = int(retries)
        self.backoff_s = float(backoff_s)
        self.max_backoff_s = float(max_backoff_s)
        self.retryable = tuple(retryable)
        self._sleep = sleep
        self.retry_attempts = 0  # total extra attempts across all calls
        self.retried_calls = 0  # calls that needed at least one retry
        self.gave_up = 0  # calls that exhausted the budget

    def __getattr__(self, name: str):
        return getattr(self.inner, name)

    @property
    def resident_bytes(self) -> int:
        return self.inner.resident_bytes

    def _call(self, fn, *args):
        attempt = 0
        while True:
            try:
                return fn(*args)
            except CorruptionError:
                raise  # fatal by contract: see repro.core.integrity
            except self.retryable:
                if attempt >= self.retries:
                    self.gave_up += 1
                    raise
                if attempt == 0:
                    self.retried_calls += 1
                self.retry_attempts += 1
                delay = min(self.backoff_s * (2 ** attempt),
                            self.max_backoff_s)
                if delay > 0:
                    self._sleep(delay)
                attempt += 1

    def gather(self, gidx: np.ndarray, depth: np.ndarray) -> np.ndarray:
        return self._call(self.inner.gather, gidx, depth)

    def read_items(self, lo: int, hi: int) -> np.ndarray:
        return self._call(self.inner.read_items, lo, hi)

    def close(self) -> None:
        self.inner.close()


class FlakyBackend(StoreBackend):
    """Deterministic fault injector: scripted transient failures on backend
    reads/gathers (the chaos harness's store faults,
    ``tests/test_crash_resume.py``).

    Failure ordinals count *successful* pass-throughs: an injected failure
    does not advance the ordinal, so a retried call fails
    ``failures_per_call`` times at the same position and then succeeds —
    the sequence of calls reaching ``inner`` is identical to a fault-free
    run, which is exactly the transparency the retry layer claims.
    ``fail_every=N`` fails every Nth call; explicit ordinals come via
    ``fail_gathers`` / ``fail_reads``.
    """

    def __init__(self, inner: StoreBackend, fail_gathers=(), fail_reads=(),
                 fail_every: int = 0, failures_per_call: int = 1):
        self.inner = inner
        self.fail_gathers = {int(x) for x in fail_gathers}
        self.fail_reads = {int(x) for x in fail_reads}
        self.fail_every = int(fail_every)
        self.failures_per_call = int(failures_per_call)
        self.gather_calls = 0
        self.read_calls = 0
        self.injected = 0
        self._fails: dict = {}

    def __getattr__(self, name: str):
        return getattr(self.inner, name)

    @property
    def resident_bytes(self) -> int:
        return self.inner.resident_bytes

    def _maybe_fail(self, kind: str, n: int, scripted) -> None:
        hit = n in scripted or (self.fail_every > 0
                                and n % self.fail_every == 0)
        c = self._fails.get((kind, n), 0)
        if hit and c < self.failures_per_call:
            self._fails[(kind, n)] = c + 1
            self.injected += 1
            raise TransientStoreError(
                f"injected {kind} fault at call {n} (#{c + 1})")

    def gather(self, gidx: np.ndarray, depth: np.ndarray) -> np.ndarray:
        self._maybe_fail("gather", self.gather_calls, self.fail_gathers)
        self.gather_calls += 1
        return self.inner.gather(gidx, depth)

    def read_items(self, lo: int, hi: int) -> np.ndarray:
        self._maybe_fail("read", self.read_calls, self.fail_reads)
        self.read_calls += 1
        return self.inner.read_items(lo, hi)

    def close(self) -> None:
        self.inner.close()


# ---------------------------------------------------------------------------
# Cross-superblock store (out-of-core merge path, core/superblock.py)
# ---------------------------------------------------------------------------


class CorpusStore:
    """Corpus window server for cross-superblock fetches.

    During the out-of-core merge (``repro.core.superblock``) a run only holds
    one superblock of 16-byte records; comparisons against suffixes of *other*
    superblocks are answered by this store — the same "raw data stays put,
    indexes move" discipline as :func:`mget_window`.  The corpus bytes live
    behind a :class:`StoreBackend` (host-resident array or budgeted
    disk-chunk cache); the store owns the device-path-mirroring semantics:

    * at most ``request_capacity`` requests are served per call;
    * :meth:`mget_window_host` serves **whole tie groups** in order (an
      oversized leading group is served alone so rounds always progress) and
      reports unserved actives for the caller's group-synchronous retry;
    * byte accounting matches :class:`FetchStats` (``index_bytes`` per
      request, derived from the address space like ``StoreSpec.index_bytes``;
      ``K * token_bytes`` per raw-window response);
    * ``peak_resident_bytes`` tracks the store-layer working set: backend
      cache + the merge frontier (cursor windows registered via
      :meth:`add_frontier`).
    """

    def __init__(self, corpus, cfg: SAConfig, request_capacity: int = 4096,
                 backend: Optional[StoreBackend] = None):
        if backend is None:
            backend = InMemoryBackend(corpus, cfg)
        self.backend = backend
        self.cfg = cfg
        self.key_words = cfg.key_words
        self.text_mode = backend.text_mode
        self.n = backend.n
        self.stride_bits = backend.stride_bits
        self.max_len = backend.max_len
        self.k = cfg.prefix_len
        self.request_capacity = max(1, int(request_capacity))
        self.token_bytes = token_bytes(cfg.vocab_size)
        self.index_bytes = index_request_bytes(self.n, self.stride_bits)
        # fetch accounting (read by the superblock merge's Footprint)
        self.requests = 0
        self.request_bytes = 0
        self.response_bytes = 0
        self.retries = 0
        self.rounds = 0
        self.peak_windows = 0
        # store-layer residency: backend cache + cursor frontier
        self.frontier_bytes = 0
        self.peak_resident_bytes = 0
        # per-superblock staging (contiguous item ranges; separate counters
        # because staged blocks are transient build input, not merge traffic)
        self.staged_items = 0
        self.staged_bytes = 0
        # seconds in the main thread's window fetches (fetch_windows,
        # fetch_keys)
        self.timers = {"t_fetch_s": 0.0}
        self._note_resident()

    @property
    def max_window_depth(self) -> int:
        """Upper bound on K-token windows any suffix comparison can consume
        (one extra all-zero window past the end resolves exhaustion)."""
        return -(-self.max_len // self.k) + 2

    # -- residency accounting ----------------------------------------------
    def _note_resident(self) -> None:
        cur = self.backend.resident_bytes + self.frontier_bytes
        if cur > self.peak_resident_bytes:
            self.peak_resident_bytes = cur

    def add_frontier(self, delta_bytes: int) -> None:
        """Register merge-frontier residency (cursor window cache deltas)."""
        self.frontier_bytes += delta_bytes
        if delta_bytes > 0:
            self._note_resident()

    # -- per-superblock staging --------------------------------------------
    def stage_read(self, lo: int, hi: int) -> np.ndarray:
        """The backend half of :meth:`stage_items`: stream the contiguous
        item range ``[lo, hi)`` without touching any store counter.

        This is the **worker-thread-safe** staging primitive: it only reads
        (backends stream the range past their window cache), so the pipeline
        worker may run it while the main thread owns the accounting state.
        Every background ``stage_read`` must be paired with a main-thread
        :meth:`note_staged` at the executor hand-off — salint SAL010 rejects
        worker-context code that mutates the gated counters directly.
        """
        return self.backend.read_items(lo, hi)

    def note_staged(self, lo: int, hi: int, nbytes: int) -> None:
        """Main-thread accounting for one staged range (the other half of
        :meth:`stage_items`, applied when a background stage is collected)."""
        self.staged_items += int(hi - lo)
        self.staged_bytes += int(nbytes)

    def stage_items(self, lo: int, hi: int) -> np.ndarray:
        """Materialize the contiguous item range ``[lo, hi)`` for in-core
        superblock construction.

        The accounted front door for block staging: backends stream the range
        without touching their window cache (``ChunkedFileBackend`` preads
        straight from disk), and the store records the staged volume in
        ``staged_items`` / ``staged_bytes`` — separate from the merge's
        request/response counters, which measure only cross-superblock window
        traffic (the paper's "indexes move, raw data stays put" quantity).
        Synchronous composition of :meth:`stage_read` + :meth:`note_staged`,
        so the pipelined and synchronous paths account identically by
        construction.
        """
        out = self.stage_read(lo, hi)
        self.note_staged(lo, hi, out.nbytes)
        return out

    # -- raw gather ---------------------------------------------------------
    def _gather(self, gidx: np.ndarray, depth: np.ndarray) -> np.ndarray:
        out = self.backend.gather(np.asarray(gidx, np.int64), depth)
        self._note_resident()
        return out

    # -- batched fetch APIs -------------------------------------------------
    def fetch_windows(self, gidx: np.ndarray, depth) -> np.ndarray:
        """Fetch windows for every request (internally split into
        capacity-sized service rounds; no retry semantics needed)."""
        m = gidx.shape[0]
        depth = np.broadcast_to(np.asarray(depth, np.int64), (m,))
        out = np.zeros((m, self.k), np.int32)
        with span("store.fetch", self.timers, "t_fetch_s"):
            for lo in range(0, m, self.request_capacity):
                hi = min(lo + self.request_capacity, m)
                out[lo:hi] = self._gather(gidx[lo:hi], depth[lo:hi])
                self.rounds += 1
                self.requests += hi - lo
                self.request_bytes += (hi - lo) * self.index_bytes
                self.response_bytes += (hi - lo) * self.k * self.token_bytes
        self.peak_windows = max(self.peak_windows, m)
        return out

    def gather_keys(self, gidx: np.ndarray, depth,
                    timers: Optional[dict] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """The backend half of :meth:`fetch_keys`: capacity-chunked backend
        gathers + key packing, **no counter or residency mutation**.

        This is the worker-thread-safe fetch primitive the merge's refill
        prefetch submits to the pipeline executor: the backend call pattern
        (one ``gather`` per capacity chunk) is identical to the synchronous
        path, but ``FetchStats`` accounting stays untouched — the collector
        applies it on the main thread via :meth:`note_fetched` at the
        hand-off (salint SAL010).  The span ``store.fetch`` adds its seconds
        to ``timers["t_fetch_s"]`` only when the caller passes its timers:
        the main thread's fetches do, the prefetch on the worker does not
        (the main thread's wait for it is the merge's collect).
        """
        m = gidx.shape[0]
        depth = np.broadcast_to(np.asarray(depth, np.int64), (m,))
        win = np.zeros((m, self.k), np.int32)
        with span("store.fetch", timers, "t_fetch_s"):
            for lo in range(0, m, self.request_capacity):
                hi = min(lo + self.request_capacity, m)
                win[lo:hi] = self.backend.gather(
                    np.asarray(gidx[lo:hi], np.int64), depth[lo:hi])
        return pack_keys_np(win, self.cfg), (win == 0).any(axis=1)

    def note_fetched(self, m: int) -> None:
        """Main-thread accounting for ``m`` windows served by
        :meth:`gather_keys`: same totals, round count, and peak tracking as
        the synchronous :meth:`fetch_windows` loop."""
        m = int(m)
        if m <= 0:
            return
        self.rounds += -(-m // self.request_capacity)
        self.requests += m
        self.request_bytes += m * self.index_bytes
        self.response_bytes += m * self.k * self.token_bytes
        self.peak_windows = max(self.peak_windows, m)
        self._note_resident()

    def fetch_keys(self, gidx: np.ndarray, depth) -> Tuple[np.ndarray, np.ndarray]:
        """Batched packed-key fetch: windows at ``depth`` packed to key words.

        Returns ``(keys, ended)``: keys (m, key_words) int32 order-preserving
        words (:func:`pack_keys_np`), ended (m,) bool — the window contained a
        ``0``, i.e. the suffix ends inside it and every deeper window is
        all-zero.  One batched store round per capacity chunk (the merge-path
        tile driver's fetch primitive; byte accounting identical to
        :meth:`fetch_windows`).  Synchronous composition of
        :meth:`gather_keys` + :meth:`note_fetched`, so the pipelined refill
        prefetch and this path account identically by construction.
        """
        keys, ended = self.gather_keys(gidx, depth, self.timers)
        self.note_fetched(gidx.shape[0])
        return keys, ended

    def rank_windows(self, keys: np.ndarray, gidx: np.ndarray) -> np.ndarray:
        """Output ranks of candidate rows under (key words..., global index).

        The host reference of the ``kernels/merge_path`` Pallas kernel: rank
        of a row = number of rows lexicographically smaller, ties broken by
        the global index (which makes rows strictly unique).  The merge-path
        driver calls this when ``cfg.use_pallas`` is off; both paths compare
        the same packed words from :func:`pack_keys_np`.
        """
        order = np.lexsort(
            (gidx,) + tuple(keys[:, w] for w in range(keys.shape[1] - 1, -1, -1))
        )
        ranks = np.empty(order.shape[0], np.int64)
        ranks[order] = np.arange(order.shape[0], dtype=np.int64)
        return ranks

    def mget_window_host(
        self,
        gidx: np.ndarray,
        depth: np.ndarray,
        active: np.ndarray,
        group: np.ndarray,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One capacity-bounded service round over active tie groups.

        Serves whole groups, in order, until ``request_capacity`` requests are
        placed; a leading group larger than the capacity is served alone
        (burst) so progress is guaranteed.  Returns ``(windows, ok)`` where
        unserved slots have ``ok == False`` and zero windows — the caller must
        not advance any group with an unserved active member (the same
        group-synchronous rule as the device pipeline).
        """
        m = gidx.shape[0]
        win = np.zeros((m, self.k), np.int32)
        ok = np.zeros(m, bool)
        act = np.flatnonzero(active)
        self.rounds += 1
        if act.size == 0:
            return win, ok
        ag = group[act]
        new_grp = np.concatenate([[True], ag[1:] != ag[:-1]])
        grp_id = np.cumsum(new_grp) - 1
        # request count through the end of each group
        end_count = np.zeros(grp_id[-1] + 1, np.int64)
        np.maximum.at(end_count, grp_id, np.arange(1, act.size + 1))
        fits = end_count <= self.request_capacity
        fits[0] = True  # oversized leading group: serve alone
        served = act[fits[grp_id]]
        win[served] = self._gather(gidx[served], depth[served])
        ok[served] = True
        self.requests += served.size
        self.request_bytes += served.size * self.index_bytes
        self.response_bytes += served.size * self.k * self.token_bytes
        self.retries += act.size - served.size
        self.peak_windows = max(self.peak_windows, served.size)
        return win, ok


class WindowCursor:
    """Per-suffix progressive packed-key cache over a :class:`CorpusStore`.

    The k-way merge (``repro.core.superblock``) compares *run heads* over and
    over: binary-search partition probes a run member against a splitter, and
    every heap sift compares the two leading suffixes of two runs.  Without a
    cache each comparison would re-fetch both windows from the store; with
    this cursor a window is fetched **once per (suffix, K-token depth)** and
    re-served from the cursor for every later comparison, so store traffic is
    one depth-0 window per suffix plus deeper windows only down to actual
    tie-breaking depth.

    Windows are cached as **packed key words** (:func:`pack_keys_np` plus an
    end-of-suffix flag computed from the raw window at fetch time) — the same
    order-preserving representation the merge-path tile kernel ranks, so the
    splitter search and the device merge share one compare path, and a cached
    entry costs ``(key_words + 1) * 4`` bytes instead of ``K * 4``.

    Fetches go through the owning store's batched APIs, so all byte/round
    accounting stays in one place; the cursor adds `cached_windows` /
    `peak_cached_windows` and registers its byte footprint with the store's
    frontier accounting (``CorpusStore.add_frontier``) — cached keys are
    *owned copies*, so a cursor entry never pins a whole fetch batch or a
    backend disk chunk in memory.  Entries are released as suffixes are
    emitted from the merge (:meth:`release`), or wholesale between merge
    phases (:meth:`release_all`, the streaming build's frontier reset).
    """

    def __init__(self, store: CorpusStore):
        self.store = store
        self._win = {}  # gidx -> [(key words, ended) at depth 0, 1, ...]
        # one cached entry: key_words packed lanes + the ended flag lane
        self.window_bytes = (store.key_words + 1) * 4
        self.cached_windows = 0
        self.peak_cached_windows = 0

    def _account(self, delta: int) -> None:
        self.cached_windows += delta
        if delta > 0:
            self.peak_cached_windows = max(
                self.peak_cached_windows, self.cached_windows)
        self.store.add_frontier(delta * self.window_bytes)

    def _pack(self, window: np.ndarray) -> Tuple[np.ndarray, bool]:
        keys = pack_keys_np(np.array(window, np.int32, copy=True),
                            self.store.cfg)
        return keys, bool((np.asarray(window) == 0).any())

    def prefetch(self, gidx: np.ndarray) -> None:
        """Batch-fetch depth-0 windows for every uncached suffix in ``gidx``
        (one capacity-chunked store round instead of per-comparison
        singletons)."""
        miss = np.array(
            [g for g in np.asarray(gidx, np.int64).tolist() if g not in self._win],
            np.int64,
        )
        if miss.size == 0:
            return
        keys, ended = self.store.fetch_keys(miss, 0)
        for i, g in enumerate(miss.tolist()):
            self._win[g] = [(keys[i].copy(), bool(ended[i]))]
        self._account(miss.size)

    def key(self, gidx: int, depth: int) -> Tuple[np.ndarray, bool]:
        """``(key words, ended)`` of ``gidx`` at ``depth`` (cached; fetched
        on miss)."""
        ws = self._win.get(gidx)
        if ws is None:
            ws = self._win[gidx] = []
        while len(ws) <= depth:
            keys, ended = self.store.fetch_keys(
                np.array([gidx], np.int64), len(ws))
            ws.append((keys[0], bool(ended[0])))
            self._account(1)
        return ws[depth]

    def offer(self, gidx: int, depth: int, window: np.ndarray) -> None:
        """Warm the cache with an externally fetched raw window (no store
        round; packed on the way in).

        Used by the host re-rank (``_refine_sort``) so windows it already
        paid for are re-served to the k-way merge instead of re-fetched.
        Depths must arrive consecutively per suffix; offers that would leave
        a gap are ignored.
        """
        ws = self._win.get(gidx)
        if ws is None:
            if depth != 0:
                return
            self._win[gidx] = [self._pack(window)]
        elif len(ws) == depth:
            ws.append(self._pack(window))
        else:
            return
        self._account(1)

    def release(self, gidx: int) -> None:
        """Drop a suffix's cached keys (call when the merge emits it)."""
        ws = self._win.pop(gidx, None)
        if ws is not None:
            self._account(-len(ws))

    def release_all(self) -> None:
        """Drop every cached entry (streaming merge's inter-phase reset:
        residency is reclaimed at the price of re-fetching on next probe)."""
        total = self.cached_windows
        self._win.clear()
        if total:
            self._account(-total)

    def less(self, a: int, b: int) -> bool:
        """Exact ``suffix(a) < suffix(b)``; equal contents tie by index.

        Progressive packed-key comparison against cached entries (word order
        equals token-window order).  Equal windows whose suffixes end inside
        them mean identical content — the global index breaks the tie (the
        oracle's ``(suffix tokens..., index)`` order).
        """
        if a == b:
            return False
        for d in range(self.store.max_window_depth):
            wa, ended = self.key(a, d)
            wb, _ = self.key(b, d)
            lt, eq = lex_less_rows(wa[None, :], wb[None, :])
            if not eq[0]:
                return bool(lt[0])
            if ended:
                return a < b
        raise RuntimeError("suffix comparison overran the window bound")


# ---------------------------------------------------------------------------
# Store-layer backend access helpers (the only sanctioned raw-read paths
# outside a CorpusStore; everything else is a salint SAL002 violation)
# ---------------------------------------------------------------------------


def stream_backend_items(backend: StoreBackend,
                         batch_items: int = 1 << 18) -> Iterator[np.ndarray]:
    """Yield the backend's items in order as bounded batches.

    Serialization/export paths use this instead of raw ``read_items`` calls
    so no corpus-sized host array ever exists: each yielded batch is at most
    ``batch_items`` items and the caller is expected to consume it before
    the next is read.
    """
    batch_items = max(1, int(batch_items))
    for lo in range(0, backend.n, batch_items):
        yield backend.read_items(lo, min(lo + batch_items, backend.n))


def backend_fingerprint(backend: StoreBackend,
                        sample_items: int = 1024) -> dict:
    """Cheap geometry + content signature of a backend's corpus.

    The build journal (``repro.core.journal``) stamps this into its
    ``begin`` record so a ``--resume`` against a *different* corpus (or a
    reshaped one) is refused instead of splicing stale runs into a fresh
    build.  Content coverage is a head sample — a fingerprint, not an
    integrity check (chunk crcs do that); lives in the store layer so the
    raw ``read_items`` stays inside store accounting's home (SAL002).
    """
    head = np.ascontiguousarray(
        backend.read_items(0, min(backend.n, int(sample_items))), np.int32)
    from repro.core.integrity import crc32_array

    return {
        "items": int(backend.n),
        "row_len": int(backend.row_len),
        "text_mode": bool(backend.text_mode),
        "head_crc": crc32_array(head),
    }


def materialize_backend(backend: StoreBackend) -> np.ndarray:
    """Whole-corpus host materialization (explicitly *not* streaming).

    The sanctioned escape hatch for paths that genuinely need the full
    corpus host-resident — e.g. converting a disk-chunked corpus to an
    in-memory reference build for oracle comparison.  Callers on bounded-
    residency paths must use :func:`stream_backend_items` or
    :meth:`CorpusStore.stage_items` instead.
    """
    if backend.n == 0:
        shape = (0,) if backend.text_mode else (0, backend.row_len)
        return np.zeros(shape, np.int32)
    return np.concatenate(list(stream_backend_items(backend)), axis=0)
