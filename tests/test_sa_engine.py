"""`repro.serve.sa_engine` vs the host-serial search reference and brute
force (hypothesis via the compat shim).

The engine answers must be bit-identical to ``core.search`` / ``core.oracle``
whatever the corpus shape (random and repetitive text, variable-length
reads), shard count, LCP availability, or store backend — including the
boundary patterns: absent tokens, the empty pattern, patterns longer than
the corpus, and sub-``1`` tokens that collide with suffix padding.  The LCP
producers are checked against Kasai (text) and the definitional pairwise
compare (reads).
"""
import os

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.config import SAConfig, SuperblockConfig
from repro.core.lcp import lcp_from_sa, pairwise_lcp
from repro.core.oracle import lcp_kasai, naive_sa_reads, naive_sa_text
from repro.core.search import locate_store, search_store
from repro.core.store import CorpusStore
from repro.serve.sa_engine import ShardedSAEngine, SuffixArrayIndex


def _brute_text(text, pat):
    p = len(pat)
    if p == 0:
        return list(range(len(text)))
    return sorted(i for i in range(len(text))
                  if list(text[i : i + p]) == list(pat))


def _brute_reads(reads, lengths, pat):
    p = len(pat)
    return sorted(
        (i, o)
        for i in range(reads.shape[0])
        for o in range(int(lengths[i]) + 1)
        if o + p <= int(lengths[i])
        and list(reads[i, o : o + p]) == list(pat)
    )


def _text_engine(text, num_shards, with_lcp):
    cfg = SAConfig(mode="text", vocab_size=max(int(text.max()), 2)
                   if text.size else 2)
    sa = naive_sa_text(text)
    store = CorpusStore(np.asarray(text, np.int32), cfg)
    lcp = lcp_from_sa(store, sa) if with_lcp else None
    return store, sa, ShardedSAEngine(store, sa, lcp=lcp,
                                      num_shards=num_shards)


@given(
    toks=st.lists(st.integers(1, 3), min_size=1, max_size=120),
    pat=st.lists(st.integers(1, 4), min_size=0, max_size=6),
    shards=st.integers(1, 3),
)
@settings(max_examples=40, deadline=None)
def test_engine_text_matches_bruteforce(toks, pat, shards):
    text = np.array(toks, np.int32)
    _, _, eng = _text_engine(text, shards, with_lcp=True)
    got = eng.locate([np.array(pat, np.int64)])[0]
    assert list(got) == _brute_text(text, pat)


@given(
    period=st.lists(st.integers(1, 2), min_size=1, max_size=3),
    reps=st.integers(2, 40),
    pat=st.lists(st.integers(1, 2), min_size=0, max_size=8),
    shards=st.integers(1, 3),
)
@settings(max_examples=30, deadline=None)
def test_engine_repetitive_text_matches_bruteforce(period, reps, pat, shards):
    """Deep shared prefixes: the LCP fast path does real work here."""
    text = np.tile(np.array(period, np.int32), reps)
    _, _, eng = _text_engine(text, shards, with_lcp=True)
    got = eng.locate([np.array(pat, np.int64)])[0]
    assert list(got) == _brute_text(text, pat)


@given(
    rows=st.lists(st.lists(st.integers(1, 3), min_size=1, max_size=7),
                  min_size=1, max_size=16),
    pat=st.lists(st.integers(1, 4), min_size=1, max_size=5),
    shards=st.integers(1, 3),
)
@settings(max_examples=30, deadline=None)
def test_engine_reads_align_matches_bruteforce(rows, pat, shards):
    l = max(len(r) for r in rows)
    lengths = np.array([len(r) for r in rows], np.int64)
    reads = np.zeros((len(rows), l), np.int32)
    for i, r in enumerate(rows):
        reads[i, : len(r)] = r
    cfg = SAConfig(mode="reads", vocab_size=3)
    sa = naive_sa_reads(reads, lengths=lengths)
    store = CorpusStore(reads, cfg)
    eng = ShardedSAEngine(store, sa, lcp=lcp_from_sa(store, sa),
                          num_shards=shards)
    got = eng.align([np.array(pat, np.int64)])[0]
    assert got == _brute_reads(reads, lengths, pat)


@pytest.mark.parametrize("shards", [1, 3])
@pytest.mark.parametrize("with_lcp", [True, False])
def test_engine_boundary_patterns(shards, with_lcp):
    rng = np.random.default_rng(5)
    text = rng.integers(1, 4, 300).astype(np.int32)
    store, sa, eng = _text_engine(text, shards, with_lcp)
    pats = [
        np.zeros(0, np.int64),                      # empty -> everything
        np.array([9], np.int64),                    # absent (over-vocab)
        np.array([0], np.int64),                    # collides with padding
        np.array([-2, 1], np.int64),
        np.concatenate([text, [1]]).astype(np.int64),  # longer than corpus
        text[:7].astype(np.int64),
    ]
    counts = eng.count(pats)
    assert int(counts[0]) == len(text)
    assert list(counts[1:5]) == [0, 0, 0, 0]
    assert int(counts[5]) == len(_brute_text(text, list(text[:7])))
    for p, occ in zip(pats, eng.locate(pats), strict=True):
        np.testing.assert_array_equal(occ, locate_store(store, sa, p))


@pytest.mark.parametrize("with_lcp", [True, False])
def test_engine_with_and_without_lcp_identical(with_lcp):
    """Acceleration must not change a single answer (and the accelerated
    engine must issue no more explicit compares than the plain one)."""
    rng = np.random.default_rng(9)
    text = np.tile(rng.integers(1, 3, 8).astype(np.int32), 60)
    store, sa, fast = _text_engine(text, 3, with_lcp=True)
    _, _, slow = _text_engine(text, 3, with_lcp=False)
    pats = [rng.integers(1, 3, int(m)).astype(np.int64)
            for m in rng.integers(0, 10, 40)]
    rf, rs = fast.ranges(pats), slow.ranges(pats)
    np.testing.assert_array_equal(rf, rs)
    assert fast.stats["compare_rounds"] <= slow.stats["compare_rounds"]
    assert fast.engine_stats()["lcp_accelerated"]


def test_engine_result_cache_hits():
    rng = np.random.default_rng(3)
    text = rng.integers(1, 4, 200).astype(np.int32)
    _, _, eng = _text_engine(text, 2, with_lcp=True)
    pats = [text[i : i + 4].astype(np.int64) for i in (0, 50, 100)]
    first = eng.count(pats)
    rounds = eng.stats["search_rounds"]
    again = eng.count(pats)
    np.testing.assert_array_equal(first, again)
    assert eng.stats["search_rounds"] == rounds  # pure cache service
    assert eng.cache.hits >= len(pats)
    # zero-budget cache never serves hits
    _, _, cold = _text_engine(text, 2, with_lcp=True)
    cold.cache.budget = 0
    cold.count(pats)
    cold.count(pats)
    assert cold.cache.hits == 0


@given(
    toks=st.lists(st.integers(1, 4), min_size=2, max_size=150),
)
@settings(max_examples=30, deadline=None)
def test_lcp_from_sa_matches_kasai_text(toks):
    text = np.array(toks, np.int32)
    cfg = SAConfig(mode="text", vocab_size=4)
    sa = naive_sa_text(text)
    store = CorpusStore(text, cfg)
    np.testing.assert_array_equal(lcp_from_sa(store, sa),
                                  lcp_kasai(text, sa))


@given(
    rows=st.lists(st.lists(st.integers(1, 2), min_size=1, max_size=6),
                  min_size=1, max_size=10),
)
@settings(max_examples=25, deadline=None)
def test_lcp_from_sa_matches_definition_reads(rows):
    l = max(len(r) for r in rows)
    lengths = np.array([len(r) for r in rows], np.int64)
    reads = np.zeros((len(rows), l), np.int32)
    for i, r in enumerate(rows):
        reads[i, : len(r)] = r
    cfg = SAConfig(mode="reads", vocab_size=2)
    sa = naive_sa_reads(reads, lengths=lengths)
    store = CorpusStore(reads, cfg)
    got = lcp_from_sa(store, sa)
    sb = store.stride_bits
    mask = (1 << sb) - 1

    def sfx(g):
        i, o = int(g) >> sb, int(g) & mask
        return list(reads[i, o : int(lengths[i])])

    for j in range(1, len(sa)):
        a, b = sfx(sa[j - 1]), sfx(sa[j])
        want = 0
        while want < min(len(a), len(b)) and a[want] == b[want]:
            want += 1
        assert int(got[j]) == want, (j, a, b)
    assert int(got[0]) == 0 if len(sa) else True
    # pairwise producer agrees with the adjacent-pair producer
    if len(sa) > 1:
        np.testing.assert_array_equal(
            pairwise_lcp(store, np.asarray(sa[:-1]), np.asarray(sa[1:])),
            got[1:])


def test_merge_emitted_lcp_matches_posthoc(tmp_path):
    """The merge's emit-order LCP == recomputing over the final SA."""
    from repro.core.superblock import build_suffix_array_superblock

    rng = np.random.default_rng(21)
    reads = rng.integers(1, 5, size=(120, 12)).astype(np.int32)
    cfg = SAConfig(vocab_size=4)
    sb = SuperblockConfig(num_superblocks=3, emit_lcp=True,
                          spill_dir=str(tmp_path / "spill"))
    res = build_suffix_array_superblock(reads, cfg=cfg, sb=sb)
    assert res.lcp is not None and res.stats["emit_lcp"]
    store = CorpusStore(reads, cfg)
    np.testing.assert_array_equal(np.asarray(res.lcp),
                                  lcp_from_sa(store, res.suffix_array))


@pytest.mark.parametrize("backend", ["chunked", "memory"])
def test_index_save_open_round_trip(tmp_path, backend):
    rng = np.random.default_rng(13)
    reads = rng.integers(1, 5, size=(60, 10)).astype(np.int32)
    cfg = SAConfig(vocab_size=4)
    idx = SuffixArrayIndex.build(reads, cfg=cfg)
    pats = [reads[7, 2:6].astype(np.int64), np.array([4, 4, 4, 4], np.int64),
            np.zeros(0, np.int64)]
    want_counts = idx.count(pats)
    want_align = idx.align(pats[0])
    d = str(tmp_path / "ix")
    idx.save(d)
    for name in ("manifest.json", "suffix_array.npy", "lcp.npy",
                 "corpus.sachunk"):
        assert os.path.exists(os.path.join(d, name)), name
    with SuffixArrayIndex.open(d, store_backend=backend) as re_ix:
        assert re_ix.lcp is not None
        np.testing.assert_array_equal(re_ix.count(pats), want_counts)
        assert re_ix.align(pats[0]) == want_align
        served = re_ix.stats()["backend"]
        if served == "SanitizingBackend":  # REPRO_SANITIZE=1 wraps it
            served = type(re_ix.store.backend.inner).__name__
        assert served == (
            "ChunkedFileBackend" if backend == "chunked"
            else "InMemoryBackend")


def test_build_with_index_dir_persists_and_reopens(tmp_path):
    """build(index_dir=...) -> a served-from-disk index; open() needs no
    rebuild even through the out-of-core path."""
    rng = np.random.default_rng(17)
    reads = rng.integers(1, 5, size=(90, 10)).astype(np.int32)
    cfg = SAConfig(vocab_size=4)
    d = str(tmp_path / "ix")
    idx = SuffixArrayIndex.build(
        reads, cfg=cfg, index_dir=d,
        sb=SuperblockConfig(num_superblocks=3, store_backend="chunked"))
    assert idx.index_dir == d
    sa_ref = naive_sa_reads(reads)
    np.testing.assert_array_equal(np.asarray(idx.sa), sa_ref)
    p = reads[3, 1:5].astype(np.int64)
    want = idx.align(p)
    idx.close()
    with SuffixArrayIndex.open(d) as re_ix:
        np.testing.assert_array_equal(np.asarray(re_ix.sa), sa_ref)
        assert re_ix.align(p) == want


def test_facade_text_mode_rejects_align():
    text = np.array([1, 2, 1, 2], np.int32)
    idx = SuffixArrayIndex.build(text, cfg=SAConfig(mode="text", vocab_size=2))
    with pytest.raises(ValueError, match="reads-mode"):
        idx.align(np.array([1], np.int64))


def test_engine_matches_search_store_on_chunked_backend(tmp_path):
    """Shared comparator end to end: engine over a disk-chunked store ==
    host-serial search over the same store."""
    rng = np.random.default_rng(29)
    text = rng.integers(1, 4, 700).astype(np.int32)
    cfg = SAConfig(mode="text", vocab_size=3)
    d = str(tmp_path / "ix")
    idx = SuffixArrayIndex.build(text, cfg=cfg, index_dir=d)
    idx.close()
    with SuffixArrayIndex.open(d, store_backend="chunked") as re_ix:
        eng = re_ix.engine
        pats = [rng.integers(1, 4, int(m)).astype(np.int64)
                for m in rng.integers(0, 9, 25)]
        got = eng.ranges(pats)
        for p, (lo, hi) in zip(pats, got, strict=True):
            assert (int(lo), int(hi)) == search_store(re_ix.store, re_ix.sa, p)
