"""SA pattern search (the index's consumer side)."""
import numpy as np

from repro.config import SAConfig
from repro.core.pipeline import build_suffix_array
from repro.core.search import align_reads, count_occurrences, find_occurrences


def test_search_text_counts_match_bruteforce():
    rng = np.random.default_rng(0)
    text = rng.integers(1, 4, size=(400,)).astype(np.int32)
    res = build_suffix_array(text, cfg=SAConfig(vocab_size=3))
    sa = res.suffix_array
    for plen in (1, 2, 3, 5):
        for _ in range(5):
            start = int(rng.integers(0, len(text) - plen))
            pat = text[start : start + plen]
            got = find_occurrences(text, sa, pat)
            want = [
                i for i in range(len(text))
                if np.array_equal(text[i : i + plen], pat)
                and i + plen <= len(text)
            ]
            assert got == want, (pat, got[:5], want[:5])
            assert count_occurrences(text, sa, pat) == len(want)


def test_search_absent_pattern():
    text = np.ones(50, np.int32)  # all 1s
    res = build_suffix_array(text, cfg=SAConfig(vocab_size=3))
    assert count_occurrences(text, res.suffix_array, [2, 1]) == 0
    assert count_occurrences(text, res.suffix_array, [1, 1]) == 49


def test_align_reads_seed_lookup():
    """The paper's application: find every (read, offset) matching a seed."""
    rng = np.random.default_rng(1)
    reads = rng.integers(1, 5, size=(40, 20)).astype(np.int32)
    res = build_suffix_array(reads, cfg=SAConfig(vocab_size=4))
    import math

    sb = int(math.ceil(math.log2(reads.shape[1] + 1)))
    seed = reads[7, 3:9]
    got = align_reads(reads, res.suffix_array, sb, seed)
    want = sorted(
        (r, o)
        for r in range(reads.shape[0])
        for o in range(reads.shape[1] - len(seed) + 1)
        if np.array_equal(reads[r, o : o + len(seed)], seed)
    )
    assert got == want
    assert (7, 3) in got
