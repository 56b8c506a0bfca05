"""Store backends: chunked-file vs in-memory equivalence + residency.

ISSUE 3 satellite coverage: the ``ChunkedFileBackend`` must serve windows
byte-identical to ``InMemoryBackend`` for arbitrary (gidx, depth) sets —
including windows straddling chunk edges and the corpus tail (hypothesis
property, via the compat shim) — while its LRU cache never exceeds the
resident-byte budget; plus the ``WindowCursor`` eviction paths
(``release``/``release_all``/``offer``) and the chunked on-disk format
roundtrip.

This file's *purpose* is exercising raw backend reads, so the SAL002
backend-encapsulation rule is suppressed file-wide.
"""
# salint: disable-file=SAL002
import os
import shutil
import tempfile

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.config import SAConfig
from repro.core.store import (
    ChunkedFileBackend,
    CorpusStore,
    InMemoryBackend,
    WindowCursor,
    index_request_bytes,
    pack_keys_np,
)
from repro.data.chunk_store import (
    ChunkedCorpusReader,
    read_chunked_corpus_meta,
    write_chunked_corpus,
)

CFG = SAConfig(vocab_size=4, chars_per_word=2, key_words=2)  # K = 4


# ---------------------------------------------------------------------------
# on-disk format
# ---------------------------------------------------------------------------


def test_chunked_corpus_roundtrip_text(tmp_path):
    rng = np.random.default_rng(0)
    text = rng.integers(1, 5, size=(101,)).astype(np.int32)  # partial tail
    p = str(tmp_path / "t.sachunk")
    meta = write_chunked_corpus(text, p, chunk_items=16)
    assert meta.text_mode and meta.items == 101 and meta.num_chunks == 7
    assert read_chunked_corpus_meta(p) == meta
    with ChunkedCorpusReader(p) as r:
        np.testing.assert_array_equal(r.read_items(0, 101), text)
        np.testing.assert_array_equal(r.read_items(20, 35), text[20:35])
        # tail chunk is short; halo past the end is zero-padded
        tail = r.read_chunk(6, halo=4)
        np.testing.assert_array_equal(tail[:5], text[96:])
        assert (tail[5:] == 0).all()


def test_chunked_corpus_roundtrip_reads(tmp_path):
    rng = np.random.default_rng(1)
    reads = rng.integers(1, 5, size=(23, 9)).astype(np.int32)
    p = str(tmp_path / "r.sachunk")
    meta = write_chunked_corpus(reads, p, chunk_items=5)
    assert not meta.text_mode and meta.row_len == 9
    with ChunkedCorpusReader(p) as r:
        np.testing.assert_array_equal(r.read_items(0, 23), reads)
        np.testing.assert_array_equal(r.read_chunk(4), reads[20:])
        with pytest.raises(ValueError):
            r.read_chunk(0, halo=2)  # rows are atomic: no halo in reads mode


def test_chunked_corpus_rejects_garbage(tmp_path):
    p = str(tmp_path / "bad")
    with open(p, "wb") as f:
        f.write(b"not a chunked corpus, definitely")
    with pytest.raises(ValueError):
        read_chunked_corpus_meta(p)


# ---------------------------------------------------------------------------
# backend equivalence (the byte-exactness acceptance property)
# ---------------------------------------------------------------------------


def _backends_text(tmp_path_str, text, chunk_items, budget=1 << 16):
    mem = InMemoryBackend(text, CFG)
    p = os.path.join(tmp_path_str, "c.sachunk")
    write_chunked_corpus(text, p, chunk_items=chunk_items)
    return mem, ChunkedFileBackend(p, CFG, cache_budget_bytes=budget)


def test_chunk_edge_and_tail_windows_exact(tmp_path):
    """Deterministic edge cases: windows starting at / straddling a chunk
    boundary, and windows running past the corpus tail."""
    rng = np.random.default_rng(2)
    text = rng.integers(1, 5, size=(50,)).astype(np.int32)
    mem, ch = _backends_text(str(tmp_path), text, chunk_items=8)
    cases = [(7, 0), (8, 0), (6, 0), (15, 0), (49, 0), (47, 0),
             (0, 12), (40, 2), (49, 13)]
    for g, d in cases:
        gi = np.array([g], np.int64)
        dd = np.array([d], np.int64)
        np.testing.assert_array_equal(
            mem.gather(gi, dd), ch.gather(gi, dd), err_msg=f"(g={g}, d={d})")


@given(
    n=st.integers(2, 120),
    chunk_items=st.integers(1, 40),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=30, deadline=None)
def test_property_chunked_text_windows_match_memory(n, chunk_items, seed):
    # no pytest fixtures here: @given examples manage their own tmp dir
    # (the hypothesis compat shim cannot inject fixtures)
    rng = np.random.default_rng(seed)
    text = rng.integers(1, 5, size=(n,)).astype(np.int32)
    d = tempfile.mkdtemp(prefix="sachunk_prop_")
    try:
        mem, ch = _backends_text(d, text, chunk_items=min(chunk_items, n))
        m = 64
        gidx = rng.integers(0, n, size=(m,)).astype(np.int64)
        # bias some requests onto chunk edges and the corpus tail
        edges = np.arange(0, n, max(1, min(chunk_items, n)), dtype=np.int64)
        gidx[: min(m, edges.size)] = edges[: min(m, edges.size)]
        gidx[-1] = n - 1
        depth = rng.integers(0, mem.max_len // mem.k + 2,
                             size=(m,)).astype(np.int64)
        np.testing.assert_array_equal(mem.gather(gidx, depth),
                                      ch.gather(gidx, depth))
        ch.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)


@given(
    r=st.integers(1, 40),
    l=st.integers(1, 12),
    chunk_items=st.integers(1, 16),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=30, deadline=None)
def test_property_chunked_reads_windows_match_memory(r, l, chunk_items, seed):
    rng = np.random.default_rng(seed)
    reads = rng.integers(1, 5, size=(r, l)).astype(np.int32)
    mem = InMemoryBackend(reads, CFG)
    d = tempfile.mkdtemp(prefix="sachunk_prop_")
    try:
        p = os.path.join(d, "c.sachunk")
        write_chunked_corpus(reads, p, chunk_items=min(chunk_items, r))
        ch = ChunkedFileBackend(p, CFG, cache_budget_bytes=1 << 16)
        m = 64
        row = rng.integers(0, r, size=(m,)).astype(np.int64)
        off = rng.integers(0, l + 1, size=(m,)).astype(np.int64)
        gidx = (row << mem.stride_bits) | off
        depth = rng.integers(0, mem.max_len // mem.k + 2,
                             size=(m,)).astype(np.int64)
        np.testing.assert_array_equal(mem.gather(gidx, depth),
                                      ch.gather(gidx, depth))
        ch.close()
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# LRU residency bound
# ---------------------------------------------------------------------------


def test_lru_cache_respects_budget_and_counts(tmp_path):
    rng = np.random.default_rng(3)
    text = rng.integers(1, 5, size=(128,)).astype(np.int32)
    p = str(tmp_path / "c.sachunk")
    write_chunked_corpus(text, p, chunk_items=16)  # 8 chunks, 80 B resident ea
    budget = 200  # fits 2 chunks (with halo), not 3
    ch = ChunkedFileBackend(p, CFG, cache_budget_bytes=budget)
    peak = 0
    for g in range(0, 128, 4):
        ch.gather(np.array([g], np.int64), np.array([0], np.int64))
        assert ch.resident_bytes <= budget
        peak = max(peak, ch.resident_bytes)
    assert peak > 0
    assert ch.evictions > 0  # the budget actually forced evictions
    # sequential sweep revisits each chunk 4x: hits must dominate misses
    assert ch.cache_hits > ch.cache_misses
    assert ch.cache_misses >= 8  # every chunk loaded at least once
    # a budget that cannot hold even one chunk is a configuration error
    with pytest.raises(ValueError):
        ChunkedFileBackend(p, CFG, cache_budget_bytes=16)


def test_lru_eviction_order_is_least_recent(tmp_path):
    text = np.arange(1, 65, dtype=np.int32) % 4 + 1
    p = str(tmp_path / "c.sachunk")
    write_chunked_corpus(text, p, chunk_items=16)  # 4 chunks
    ch = ChunkedFileBackend(p, CFG, cache_budget_bytes=200)  # 2 chunks max

    def touch(g):
        ch.gather(np.array([g], np.int64), np.array([0], np.int64))

    touch(0)   # chunk 0: miss
    touch(16)  # chunk 1: miss (cache: 0, 1)
    touch(0)   # chunk 0: hit, refreshed
    touch(32)  # chunk 2: miss, evicts chunk 1 (least recent)
    assert ch.cache_misses == 3 and ch.cache_hits == 1
    touch(0)   # still cached
    assert ch.cache_hits == 2
    touch(16)  # chunk 1 was evicted: miss again
    assert ch.cache_misses == 4


# ---------------------------------------------------------------------------
# gather visit order: resident chunks first, then the missing ones
# ---------------------------------------------------------------------------


def _chunked(tmp_path, corpus, chunk_items, budget_chunks):
    """A chunked backend over ``corpus`` whose budget holds exactly
    ``budget_chunks`` whole chunks (halo included in text mode)."""
    p = str(tmp_path / "c.sachunk")
    meta = write_chunked_corpus(corpus, p, chunk_items=chunk_items)
    full = meta.chunk_bytes + (CFG.prefix_len * 4 if meta.text_mode else 0)
    return ChunkedFileBackend(p, CFG, cache_budget_bytes=budget_chunks * full)


@pytest.mark.parametrize("text_mode", [False, True], ids=["reads", "text"])
@pytest.mark.parametrize("budget_chunks", [3, 12], ids=["evicting", "roomy"])
def test_gather_order_windows_exact(tmp_path, text_mode, budget_chunks):
    """Windows are bit-identical to the whole-corpus reference under any
    visit order, and the budget holds after every call."""
    rng = np.random.default_rng(7)
    if text_mode:  # 10 chunks of 16 tokens, a 4-token tail
        corpus = rng.integers(1, 5, size=(164,)).astype(np.int32)
        ch = _chunked(tmp_path, corpus, 16, budget_chunks)
    else:  # 10 chunks of 4 rows, a 2-row tail
        corpus = rng.integers(1, 5, size=(42, 9)).astype(np.int32)
        ch = _chunked(tmp_path, corpus, 4, budget_chunks)
    mem = InMemoryBackend(corpus, CFG)
    assert ch.num_chunks == 11
    for _ in range(12):
        m = int(rng.integers(1, 12))
        if text_mode:
            gidx = rng.integers(0, mem.n, size=(m,)).astype(np.int64)
            # windows straddling a chunk edge, served from the halo
            edges = np.arange(13, mem.n, 16, dtype=np.int64)
            k = min(m, edges.size)
            gidx[:k] = rng.choice(edges, size=k, replace=False)
        else:
            row = rng.integers(0, mem.n, size=(m,)).astype(np.int64)
            off = rng.integers(0, mem.row_len + 1, size=(m,)).astype(np.int64)
            gidx = (row << mem.stride_bits) | off
        depth = rng.integers(0, mem.max_len // mem.k + 2,
                             size=(m,)).astype(np.int64)
        np.testing.assert_array_equal(ch.gather(gidx, depth),
                                      mem.gather(gidx, depth))
        assert ch.resident_bytes <= ch.cache_budget_bytes
    assert ch.reordered_gathers > 0
    assert (ch.evictions > 0) == (budget_chunks < ch.num_chunks)
    ch.close()


@pytest.mark.parametrize("budget_chunks", [1, 3, 5, 7])
def test_repeated_sweep_misses_only_what_was_not_resident(tmp_path,
                                                          budget_chunks):
    """A sweep over C chunks with room for B < C: every sweep after the
    first misses exactly C - B (an ascending order would miss all C)."""
    reads = np.random.default_rng(5).integers(
        1, 5, size=(32, 6)).astype(np.int32)
    ch = _chunked(tmp_path, reads, 4, budget_chunks)  # C = 8
    c = ch.num_chunks
    first = np.arange(reads.shape[0], dtype=np.int64) << ch.stride_bits
    ch.gather(first, 0)
    assert (ch.cache_misses, ch.cache_hits) == (c, 0)
    for sweep in range(1, 4):
        misses, hits, ev = ch.cache_misses, ch.cache_hits, ch.evictions
        ch.gather(first, 0)
        assert ch.cache_misses - misses == c - budget_chunks
        assert ch.cache_hits - hits == budget_chunks
        assert ch.evictions - ev == c - budget_chunks
        assert ch.resident_bytes <= ch.cache_budget_bytes
    assert ch.reordered_gathers == 3
    ch.close()


def test_reordered_gathers_counts_exactly(tmp_path):
    reads = np.arange(1, 97, dtype=np.int32).reshape(16, 6) % 4 + 1
    ch = _chunked(tmp_path, reads, 4, 2)  # 4 chunks, room for 2

    def touch(*chunks):
        rows = np.array([4 * c for c in chunks], np.int64)
        ch.gather(rows << ch.stride_bits, 0)
        return ch.reordered_gathers, ch.cache_misses, ch.cache_hits

    assert touch(0) == (0, 1, 0)
    assert touch(1) == (0, 2, 0)  # cache: 0, 1
    assert touch(0, 1, 2) == (0, 3, 2)  # already ascending; evicts 0
    assert touch(0, 2) == (1, 4, 3)  # 2 served before 0 loads; evicts 1
    assert touch(0, 3) == (1, 5, 4)  # ascending; evicts 2
    assert touch(1, 3) == (2, 6, 5)  # 3 served before 1 loads; evicts 0
    assert touch(3, 1) == (2, 6, 7)  # both resident: ascending
    ch.close()


# ---------------------------------------------------------------------------
# WindowCursor eviction paths + store frontier accounting
# ---------------------------------------------------------------------------


def _cursor_store(text=None):
    if text is None:
        text = np.ones(24, np.int32)  # all-equal: deep windows available
    store = CorpusStore(text, CFG, request_capacity=64)
    return store, WindowCursor(store)


def test_cursor_release_returns_frontier_bytes():
    store, cur = _cursor_store()
    cur.prefetch(np.array([0, 1, 2], np.int64))
    assert cur.cached_windows == 3
    assert store.frontier_bytes == 3 * cur.window_bytes
    cur.key(0, 2)  # deepen suffix 0 to depth 2 (two more entries)
    assert cur.cached_windows == 5
    cur.release(0)  # whole chain (3 entries) released at once
    assert cur.cached_windows == 2
    assert store.frontier_bytes == 2 * cur.window_bytes
    cur.release(0)  # double release is a no-op
    assert cur.cached_windows == 2
    cur.release_all()
    assert cur.cached_windows == 0 and store.frontier_bytes == 0
    # peak kept the high-water mark
    assert cur.peak_cached_windows == 5
    assert store.peak_resident_bytes >= store.backend.resident_bytes


def test_cursor_offer_rejects_gaps_and_accounts():
    store, cur = _cursor_store()
    w = np.ones(store.k, np.int32)
    pre = store.requests
    cur.offer(7, 1, w)  # depth 1 before depth 0: ignored
    assert cur.cached_windows == 0
    cur.offer(7, 0, w)
    cur.offer(7, 1, w)
    cur.offer(7, 3, w)  # gap (depth 2 missing): ignored
    cur.offer(7, 1, w)  # duplicate depth: ignored
    assert cur.cached_windows == 2
    assert store.frontier_bytes == 2 * cur.window_bytes
    assert store.requests == pre  # offers never hit the store
    # offered windows are packed on the way in and re-served without a fetch
    keys, ended = cur.key(7, 1)
    np.testing.assert_array_equal(keys, pack_keys_np(w, CFG))
    assert not ended  # no zero token: the suffix continues past the window
    assert store.requests == pre
    cur.release(7)
    assert cur.cached_windows == 0 and store.frontier_bytes == 0


def test_cursor_offered_window_is_an_owned_copy():
    store, cur = _cursor_store()
    w = np.ones(store.k, np.int32)
    want = pack_keys_np(w, CFG).copy()
    cur.offer(9, 0, w)
    w[:] = 99  # mutating the caller's buffer must not corrupt the cache
    np.testing.assert_array_equal(cur.key(9, 0)[0], want)


def test_cursor_less_matches_window_semantics():
    """Packed-key compare == raw token-window compare: suffixes of an
    all-equal text order purely by index (deep ties), and a mixed text
    orders by first differing token."""
    store, cur = _cursor_store()
    assert cur.less(3, 1)  # suffix(3) is a proper prefix of suffix(1)
    assert not cur.less(1, 3)
    text = np.array([2, 1, 3, 1, 2], np.int32)
    store2 = CorpusStore(text, CFG, request_capacity=64)
    cur2 = WindowCursor(store2)
    order = sorted(range(5), key=lambda i: (list(text[i:]) + [0], i))
    for a, b in zip(order, order[1:], strict=False):
        assert cur2.less(a, b) and not cur2.less(b, a)


def test_index_request_bytes_derivation():
    # 31-bit address spaces ship one int32 word; wider ship two
    assert index_request_bytes(480, 0) == 4
    assert index_request_bytes(48, 4) == 4
    assert index_request_bytes(1 << 28, 4) == 8  # 28 + 4 bits > 31
    # CorpusStore derives its own width and accounts with it
    store, _ = _cursor_store()
    store.fetch_windows(np.arange(6, dtype=np.int64), 0)
    assert store.index_bytes == 4
    assert store.request_bytes == 6 * store.index_bytes
