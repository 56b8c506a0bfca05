"""System tests: the paper's scheme vs the exact oracle (single device)."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.config import SAConfig
from repro.core.oracle import (
    doubling_sa_text,
    lcp_kasai,
    naive_sa_reads,
    naive_sa_text,
)
from repro.core.pipeline import build_suffix_array
from repro.core.prefix_doubling import build_suffix_array_doubling
from repro.core.terasort import build_suffix_array_terasort

CFG_DNA = SAConfig(vocab_size=4, chars_per_word=2, key_words=2)  # K=4: forces rounds


def test_table1_sinica():
    """Paper Table I: SA of SINICA$ (alphabet-mapped)."""
    # A=1 C=2 I=3 N=4 S=5 ; $ is the implicit terminator
    text = np.array([5, 3, 4, 3, 2, 1], np.int32)
    res = build_suffix_array(text, cfg=SAConfig(vocab_size=5, chars_per_word=3))
    np.testing.assert_array_equal(res.suffix_array, [5, 4, 3, 1, 2, 0])


def test_reads_random_matches_oracle():
    rng = np.random.default_rng(0)
    reads = rng.integers(1, 5, size=(60, 15)).astype(np.int32)
    res = build_suffix_array(reads, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads))
    assert res.stats["dropped"] == 0
    assert res.stats["unresolved"] == 0


def test_reads_variable_lengths():
    rng = np.random.default_rng(1)
    lens = rng.integers(0, 11, size=(25,)).astype(np.int32)
    reads = np.zeros((25, 11), np.int32)
    for i, n in enumerate(lens):
        reads[i, :n] = rng.integers(1, 5, size=(n,))
    res = build_suffix_array(reads, lengths=lens, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads, lens))


def test_reads_duplicates_stable_order():
    rng = np.random.default_rng(2)
    base = rng.integers(1, 5, size=(4, 9)).astype(np.int32)
    reads = np.tile(base, (4, 1))
    res = build_suffix_array(reads, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads))


def test_paired_end_two_files():
    """Paper Case 6: pair-end = two input files, reads concatenated."""
    rng = np.random.default_rng(3)
    fwd = rng.integers(1, 5, size=(20, 12)).astype(np.int32)
    rev = fwd[:, ::-1].copy()
    both = np.concatenate([fwd, rev], axis=0)
    res = build_suffix_array(both, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(both))


def test_text_mode_matches_oracle():
    rng = np.random.default_rng(4)
    text = rng.integers(1, 5, size=(300,)).astype(np.int32)
    res = build_suffix_array(text, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, doubling_sa_text(text))


def test_text_repetitive():
    text = np.tile(np.array([1, 2, 1], np.int32), 40)
    res = build_suffix_array(text, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, naive_sa_text(text))


def test_paper_faithful_mode():
    """base packing + raw-window responses + skip-exhausted (paper §IV)."""
    rng = np.random.default_rng(5)
    reads = rng.integers(1, 5, size=(40, 13)).astype(np.int32)
    cfg = SAConfig(
        vocab_size=4, chars_per_word=2, key_words=2,
        packing="base", server_pack=False,
    )
    res = build_suffix_array(reads, cfg=cfg)
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads))
    # paper-faithful responses ship raw windows: response bytes = K per request
    assert res.footprint.fetch_response == res.stats["fetch_requests"] * 4


def test_terasort_baseline_matches_oracle():
    rng = np.random.default_rng(6)
    reads = rng.integers(1, 5, size=(50, 14)).astype(np.int32)
    res = build_suffix_array_terasort(reads, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads))


def test_scheme_shuffles_less_than_terasort():
    """The paper's core claim: index-only shuffle << materialized shuffle."""
    rng = np.random.default_rng(7)
    reads = rng.integers(1, 5, size=(50, 30)).astype(np.int32)
    cfg = SAConfig(vocab_size=4)
    scheme = build_suffix_array(reads, cfg=cfg)
    tera = build_suffix_array_terasort(reads, cfg=cfg)
    np.testing.assert_array_equal(scheme.suffix_array, tera.suffix_array)
    assert scheme.footprint.shuffle < tera.footprint.shuffle
    # 16-byte records vs (L+1 + 8)-byte materialized suffixes
    assert scheme.footprint.shuffle / tera.footprint.shuffle == pytest.approx(
        16 / (31 + 8)
    )
    assert tera.footprint.materialized > 0 and scheme.footprint.materialized == 0


def test_doubling_matches_oracle():
    rng = np.random.default_rng(8)
    text = rng.integers(1, 5, size=(400,)).astype(np.int32)
    res = build_suffix_array_doubling(text, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, doubling_sa_text(text))


def test_doubling_pathological_beats_scheme_rounds():
    """Beyond-paper claim: O(log n) rounds vs O(LCP/K) on repetitive text."""
    text = np.tile(np.array([1, 2], np.int32), 100)
    cfg = SAConfig(vocab_size=4, chars_per_word=2, key_words=2)
    scheme = build_suffix_array(text, cfg=cfg)
    dbl = build_suffix_array_doubling(text, cfg=cfg)
    np.testing.assert_array_equal(scheme.suffix_array, dbl.suffix_array)
    assert dbl.stats["rounds"] < scheme.stats["rounds"]


def test_doubling_on_reads_uses_separators():
    """Regression (ISSUE 2): flattening a reads corpus for the doubling
    builder must insert $ separators — a bare ``reshape(-1)`` lets suffixes
    span read boundaries, so patterns straddling two reads are "found" and
    the result is not comparable to the reads-mode pipelines."""
    from repro.core.search import count_occurrences
    from repro.data.corpus import flatten_reads_with_separators

    rng = np.random.default_rng(11)
    reads = rng.integers(1, 5, size=(20, 6)).astype(np.int32)
    flat = flatten_reads_with_separators(reads)
    assert flat.shape == (20 * 7,)
    # the separated stream is still an exact SA build
    res = build_suffix_array_doubling(flat, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, doubling_sa_text(flat))

    # a pattern spanning a read boundary exists in the bare flattening but
    # must NOT be found in the separated stream
    bare = reads.reshape(-1)
    bres = build_suffix_array_doubling(bare, cfg=CFG_DNA)
    spanning = reads[np.arange(2), [-1, 0]]  # last token of read 0 + first of read 1
    assert count_occurrences(bare, bres.suffix_array, spanning) >= 1
    # in-read counts agree with the read-set semantics for every 2-gram
    for pat in ([1, 2], [3, 4], list(spanning)):
        want = sum(
            1
            for r in range(reads.shape[0])
            for o in range(reads.shape[1] - 1)
            if list(reads[r, o : o + 2]) == list(pat)
        )
        assert count_occurrences(flat, res.suffix_array, pat) == want


def test_flatten_reads_with_separators_variable_lengths():
    from repro.data.corpus import flatten_reads_with_separators

    reads = np.array([[1, 2, 3], [4, 0, 0]], np.int32)
    lens = np.array([3, 1], np.int32)
    got = flatten_reads_with_separators(reads, lens)
    np.testing.assert_array_equal(got, [1, 2, 3, 0, 4, 0])


def test_lcp_kasai_matches_naive():
    rng = np.random.default_rng(9)
    text = rng.integers(1, 5, size=(120,)).astype(np.int32)
    sa = naive_sa_text(text)
    lcp = lcp_kasai(text, sa)
    for i in range(1, len(sa)):
        a, b = text[sa[i - 1] :], text[sa[i] :]
        m = 0
        while m < min(len(a), len(b)) and a[m] == b[m]:
            m += 1
        assert lcp[i] == m


def _lcp_cases():
    rng = np.random.default_rng(12)
    lens = rng.integers(0, 11, size=(25,)).astype(np.int32)
    varlen = np.zeros((25, 11), np.int32)
    for i, n in enumerate(lens):
        varlen[i, :n] = rng.integers(1, 5, size=(n,))
    one = rng.integers(1, 5, size=(1, 60)).astype(np.int32)
    capped = np.concatenate(
        [np.tile(one, (3, 1)), rng.integers(1, 5, size=(5, 60))]
    ).astype(np.int32)
    bits = SAConfig(vocab_size=4, chars_per_word=3, key_words=2,
                    packing="bits")
    return {
        "uniform-reads": (rng.integers(1, 5, size=(60, 15)), None, CFG_DNA),
        "uniform-reads-bits": (rng.integers(1, 5, size=(40, 13)), None, bits),
        "varlen-reads": (varlen, lens, CFG_DNA),
        "text": (rng.integers(1, 5, size=(300,)), None, CFG_DNA),
        "atat-text": (np.tile(np.array([1, 4]), 120), None, CFG_DNA),
        "duplicate-reads": (
            np.tile(rng.integers(1, 5, size=(4, 9)), (4, 1)), None, CFG_DNA),
        # identical 60-token reads stay tied past the round cap
        "round-cap": (capped, None, SAConfig(
            vocab_size=4, chars_per_word=2, key_words=2, max_rounds=1)),
    }


def _as_text(corpus, lens, sa):
    """The corpus as one text and the SA as positions in it: each read
    ends in a separator below every token and below later reads' ones, so
    the order and the LCP are the read set's."""
    if corpus.ndim == 1:
        return corpus, sa
    r, l = corpus.shape
    lens = np.full(r, l) if lens is None else np.asarray(lens)
    stride = int(np.ceil(np.log2(l + 1)))
    start = np.concatenate([[0], np.cumsum(lens + 1)[:-1]])
    text = np.concatenate(
        [np.append(corpus[i, : lens[i]], i - r) for i in range(r)])
    return text, start[sa >> stride] + (sa & ((1 << stride) - 1))


@pytest.mark.parametrize("case", list(_lcp_cases()))
def test_device_lcp_matches_kasai(case):
    """The LCP the device names, finished on the host, is the reference's;
    the device names every pair but those it cannot see."""
    from repro.core.lcp import finish_lcp, lcp_from_sa
    from repro.core.store import CorpusStore
    from repro.core.types import LCP_UNNAMED

    corpus, lens, cfg = _lcp_cases()[case]
    corpus = np.asarray(corpus, np.int32)
    res = build_suffix_array(corpus, lengths=lens, cfg=cfg, lcp=True)
    sa, n = res.suffix_array, res.suffix_array.shape[0]
    if corpus.ndim == 1:
        np.testing.assert_array_equal(sa, naive_sa_text(corpus))
    else:
        np.testing.assert_array_equal(sa, naive_sa_reads(corpus, lens))
    assert res.lcp.dtype == np.int64 and res.lcp.shape == (n,)
    unnamed = int((res.lcp[1:] == LCP_UNNAMED).sum())
    store = CorpusStore(corpus, cfg)
    host = finish_lcp(store, sa, res.lcp)
    assert host == unnamed
    assert res.stats["lcp_device_pairs"] + host == n - 1
    np.testing.assert_array_equal(res.lcp, lcp_kasai(*_as_text(corpus, lens,
                                                                sa)))
    np.testing.assert_array_equal(res.lcp, lcp_from_sa(store, sa))
    if case == "round-cap":
        assert res.stats["unresolved"] > 0 and host > 0
    elif case in ("varlen-reads", "duplicate-reads"):
        # identical suffixes: by fetches where lengths vary, else by length
        assert (host > 0) == (case == "varlen-reads")
    else:
        assert host == 0


@pytest.mark.parametrize("merge_backend", ["host", "device"])
def test_out_of_core_blocks_compile_without_lcp(monkeypatch, merge_backend):
    """An out-of-core build's blocks and its device refiner never carry the
    LCP (the merge's sink computes it): their programs are the ones traced
    without it, and the published LCP is still the reference's."""
    from repro.config import SuperblockConfig
    from repro.core import pipeline, superblock

    flags, loops, block_stats = [], [], []
    make, refine = pipeline.make_pipeline, pipeline._refine_tie_groups
    build = superblock.build_suffix_array

    def spy_make(*a, **kw):
        flags.append(kw.get("lcp", False))
        return make(*a, **kw)

    def spy_refine(*a, **kw):
        loops.append(kw.get("lcp"))
        return refine(*a, **kw)

    def spy_build(*a, **kw):
        res = build(*a, **kw)
        block_stats.append(res.stats)
        return res

    monkeypatch.setattr(pipeline, "make_pipeline", spy_make)
    monkeypatch.setattr(pipeline, "_refine_tie_groups", spy_refine)
    monkeypatch.setattr(superblock, "build_suffix_array", spy_build)
    reads = np.random.default_rng(13).integers(1, 5, size=(48, 12))
    reads = reads.astype(np.int32)
    sb = SuperblockConfig(num_superblocks=3, emit_lcp=True,
                          merge_backend=merge_backend)
    res = superblock.build_suffix_array_superblock(reads, cfg=CFG_DNA, sb=sb)
    assert len(block_stats) == 3 and flags == [False] * 3
    assert all("lcp_device_pairs" not in s for s in [*block_stats, res.stats])
    assert all(x is None for x in loops)
    if merge_backend == "device":
        assert loops  # the refiner traced its loop here, without the LCP
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads))
    np.testing.assert_array_equal(
        np.asarray(res.lcp),
        lcp_kasai(*_as_text(reads, None, res.suffix_array)))


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------


@given(
    data=st.lists(st.integers(1, 4), min_size=2, max_size=120),
)
@settings(max_examples=20, deadline=None)
def test_property_text_sa_is_sorted_permutation(data):
    text = np.array(data, np.int32)
    res = build_suffix_array(text, cfg=CFG_DNA)
    sa = res.suffix_array
    # permutation of all positions
    assert sorted(sa.tolist()) == list(range(len(text)))
    # suffixes actually sorted
    for i in range(1, len(sa)):
        assert tuple(text[sa[i - 1] :]) <= tuple(text[sa[i] :])


@given(
    r=st.integers(1, 12),
    l=st.integers(1, 10),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=15, deadline=None)
def test_property_reads_sa_matches_oracle(r, l, seed):
    rng = np.random.default_rng(seed)
    reads = rng.integers(1, 5, size=(r, l)).astype(np.int32)
    res = build_suffix_array(reads, cfg=CFG_DNA)
    np.testing.assert_array_equal(res.suffix_array, naive_sa_reads(reads))
