"""Fork equivalence: every build path today's options select publishes the
oracle's index.

Each case builds through ``SuffixArrayIndex.build(..., index_dir=...)``,
reads the published ``suffix_array.npy`` and ``lcp.npy`` back from disk and
compares them with ``core/oracle``: ``naive_sa_reads`` and the definitional
LCP of adjacent suffixes for reads, ``doubling_sa_text`` and ``lcp_kasai``
for text.  The grid is corpora x paths; a path is one side of a fork in
``SAConfig`` / ``SuperblockConfig`` (shuffle capacity, Pallas kernels,
single pass or out of core, store backend, merge backend).  Combinations
the code refuses (the device merge over the chunked store) are not in it.
"""
import os

import numpy as np
import pytest

from repro.config import SAConfig, SuperblockConfig
from repro.core.oracle import doubling_sa_text, lcp_kasai, naive_sa_reads
from repro.data.corpus import synth_dna_reads, synth_token_corpus
from repro.serve.sa_engine import SuffixArrayIndex

DNA = dict(vocab_size=4)
# K = 4 tokens a key: ties run past the first key, so refinement rounds,
# the merge's tie escalation and the device's LCP naming all do work
SHORT_KEYS = dict(chars_per_word=2, key_words=2)


def _varlen_reads():
    rng = np.random.default_rng(5)
    lens = rng.integers(0, 13, size=(40,)).astype(np.int32)
    reads = np.zeros((40, 12), np.int32)
    for i, n in enumerate(lens):
        reads[i, :n] = rng.integers(1, 5, size=(n,))
    return reads, lens


CORPORA = {
    "dna-reads": lambda: (synth_dna_reads(48, 16, seed=3), None, DNA),
    "varlen-reads": lambda: (*_varlen_reads(), DNA),
    "token-text": lambda: (synth_token_corpus(600, 4, seed=4)[0], None, DNA),
    "periodic-text": lambda: (np.tile(np.array([1, 3, 2], np.int32), 120),
                              None, DNA),
}

# name -> (SAConfig overrides, SuperblockConfig or None for single pass)
PATHS = {
    "single-adaptive": (dict(adaptive=True), None),
    "single-static": (dict(adaptive=False, shuffle_slack=2.0), None),
    "single-pallas": (dict(use_pallas=True), None),
    "ooc-memory": ({}, dict(store_backend="memory")),
    "ooc-chunked": ({}, dict(store_backend="chunked", pipeline_depth=1)),
    "ooc-device": ({}, dict(store_backend="memory", merge_backend="device")),
}


def _reads_lcp(reads, lens, sa, stride_bits):
    """LCP of each adjacent pair of read suffixes, token by token."""
    r, l = reads.shape
    lens = np.full(r, l) if lens is None else np.asarray(lens)
    mask = (1 << stride_bits) - 1

    def suffix(g):
        i, o = int(g) >> stride_bits, int(g) & mask
        return reads[i, o: int(lens[i])]

    want = np.zeros(len(sa), np.int64)
    for j in range(1, len(sa)):
        a, b = suffix(sa[j - 1]), suffix(sa[j])
        m = min(len(a), len(b))
        diff = np.flatnonzero(a[:m] != b[:m])
        want[j] = diff[0] if len(diff) else m
    return want


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("corpus_name", list(CORPORA))
def test_build_path_publishes_oracle_index(tmp_path, corpus_name, path):
    corpus, lens, base = CORPORA[corpus_name]()
    corpus = np.asarray(corpus, np.int32)
    overrides, sb_kw = PATHS[path]
    cfg = SAConfig(**base, **SHORT_KEYS, **overrides)
    sb = (SuperblockConfig() if sb_kw is None
          else SuperblockConfig(num_superblocks=3, **sb_kw))
    index_dir = str(tmp_path / "ix")

    idx = SuffixArrayIndex.build(corpus, lengths=lens, cfg=cfg, sb=sb,
                                 index_dir=index_dir)
    try:
        ooc = idx.build_stats.get("superblocks", 1) > 1
        stride_bits = idx.store.stride_bits if corpus.ndim == 2 else 0
    finally:
        idx.close()
    assert ooc == (sb_kw is not None), idx.build_stats.get("superblocks")

    sa = np.load(os.path.join(index_dir, "suffix_array.npy"))
    lcp = np.load(os.path.join(index_dir, "lcp.npy"))
    if corpus.ndim == 1:
        want_sa = doubling_sa_text(corpus)
        want_lcp = lcp_kasai(corpus, want_sa)
    else:
        want_sa = naive_sa_reads(corpus, lens)
        want_lcp = _reads_lcp(corpus, lens, want_sa, stride_bits)
    np.testing.assert_array_equal(sa, want_sa)
    np.testing.assert_array_equal(lcp, want_lcp)
