"""The plain reference against the program's own oracle, and its checks
against planted defects."""
import numpy as np
import pytest

from bench import traffic
from bench.reference import sa_check


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
@pytest.mark.parametrize("shape", [(30, 12), (64, 24), (7, 200)])
def test_suffix_array_matches_naive_oracle(seed, shape):
    from repro.core.oracle import naive_sa_reads

    reads = traffic.dna_reads(*shape, seed=traffic.derive_seed(seed, 9))
    np.testing.assert_array_equal(sa_check.suffix_array(reads),
                                  naive_sa_reads(reads))


def test_index_defects_accept_the_oracle_and_count_faults():
    from repro.core.oracle import naive_sa_reads

    reads = traffic.dna_reads(50, 30, seed=4)
    sa = naive_sa_reads(reads)
    stride = sa_check.stride_bits(30)
    bad, lcp = sa_check.adjacent_order(sa, reads, stride)
    assert bad == 0
    assert sa_check.index_defects(sa, lcp, reads) == {"sa": 0, "lcp": 0}
    swapped = sa.copy()
    swapped[[100, 101]] = swapped[[101, 100]]
    assert sa_check.index_defects(swapped, lcp, reads)["sa"] > 0
    assert sa_check.index_defects(sa[::2], lcp[::2], reads)["sa"] > 0
    dup = sa.copy()
    dup[5] = dup[6]
    assert sa_check.permutation_defects(dup, 50, 30, stride) == 2
    off = lcp.copy()
    off[7] += 1
    assert sa_check.index_defects(sa, off, reads)["lcp"] == 1


def test_kmer_oracle_and_lookup_defects():
    reads = traffic.dna_reads(40, 30, seed=8)
    stride = sa_check.stride_bits(30)
    oracle = sa_check.KmerOracle(reads, 5, stride)
    pats = np.stack([reads[3, 2:7], reads[9, 20:25],
                     np.array([4, 4, 4, 4, 4])]).astype(np.int64)
    want = []
    for p in pats:
        hits = [(r << stride) | o for r in range(40) for o in range(26)
                if np.array_equal(reads[r, o:o + 5], p)]
        want.append(np.array(hits, np.int64))
    assert list(oracle.counts(pats)) == [w.size for w in want]
    for got, w in zip(oracle.locate(pats), want, strict=True):
        np.testing.assert_array_equal(got, w)
    counts = oracle.counts(pats)
    located = oracle.locate(pats)
    ok = sa_check.lookup_defects(pats, counts, located, oracle)
    assert ok == {"count": 0, "locate": 0, "queries": 0}
    counts[0] += 1
    bad = sa_check.lookup_defects(pats, counts, located[:2], oracle)
    assert bad == {"count": 1, "locate": 1, "queries": 2}
