"""Exact-substring dedup over the suffix array (``repro.data.dedup``)."""
import numpy as np


def test_dedup_finds_planted_duplicates():
    from repro.config import SAConfig
    from repro.data.corpus import synth_token_corpus
    from repro.data.dedup import dedup_corpus

    toks, planted = synth_token_corpus(2000, 64, seed=1, dup_fraction=0.06,
                                       dup_span=40)
    _, keep, stats = dedup_corpus(
        toks, min_len=32, cfg=SAConfig(vocab_size=64, packing="bits"),
        mode="doubling",
    )
    assert stats["num_spans"] > 0
    for src, dst, span in planted:
        if np.array_equal(toks[src:src + span], toks[dst:dst + span]):
            assert not (keep[src:src + span].all() and keep[dst:dst + span].all())
    # no false positives on the untouched prefix region? (weak check: most
    # tokens survive)
    assert keep.mean() > 0.8


def test_dedup_modes_agree():
    from repro.config import SAConfig
    from repro.data.corpus import synth_token_corpus
    from repro.data.dedup import find_duplicate_spans

    toks, _ = synth_token_corpus(600, 16, seed=2, dup_fraction=0.05,
                                 dup_span=48)
    cfg = SAConfig(vocab_size=16, packing="bits")
    a = set(find_duplicate_spans(toks, 40, cfg, mode="scheme"))
    b = set(find_duplicate_spans(toks, 40, cfg, mode="doubling"))
    assert a == b
