"""The traffic generator: same seed, same inputs; the mix as its file
states it."""
import numpy as np
import pytest

from bench import traffic
from bench.reference import sa_check

from conftest import small_cell


@pytest.mark.parametrize("seed", [0, -3, 2**31 + 1, 2**40])
def test_same_seed_same_inputs(seed):
    cfg = small_cell("grouper-reads.build").config
    a = traffic.job_reads(cfg, seed, 2)
    np.testing.assert_array_equal(a, traffic.job_reads(cfg, seed, 2))
    assert a.shape == (cfg["reads"], cfg["read_len"]) and a.dtype == np.int32
    assert a.min() >= 1 and a.max() <= 4
    assert not np.array_equal(a, traffic.job_reads(cfg, seed, 3))
    assert not np.array_equal(a, traffic.job_reads(cfg, seed, 2, warmup=True))


def test_reads_match_the_program_sampler():
    from repro.data.corpus import synth_dna_reads

    np.testing.assert_array_equal(traffic.dna_reads(300, 50, seed=17),
                                  synth_dna_reads(300, 50, seed=17))


def _stream(cfg, spec, seed):
    genome, reads = traffic.job_genome_reads(cfg, seed, 0)
    return reads, traffic.QueryStream(genome, cfg["read_len"], spec, seed)


@pytest.mark.parametrize("seed", [5, 2**31 + 9])
def test_query_batches(seed):
    cell = small_cell("grouper-seed-index.lookup")
    cfg, spec = cell.config, cell.traffic
    reads, stream = _stream(cfg, spec, seed)
    b = stream.batch(4)
    assert b.shape == (cfg["read_len"] - spec["k"] + 1, spec["k"])
    assert b.min() >= 1 and b.max() <= 4
    # consecutive seeds are the windows of one read
    np.testing.assert_array_equal(b[1:, :-1], b[:-1, 1:])
    np.testing.assert_array_equal(b, _stream(cfg, spec, seed)[1].batch(4))
    assert not np.array_equal(b, stream.batch(5))
    assert not np.array_equal(b, stream.batch(4, warmup=True))


def test_error_rate_sets_the_absent_seeds():
    cell = small_cell("grouper-seed-index.lookup")
    cfg = dict(cell.config, reads=400, read_len=200)
    spec = cell.traffic
    reads, stream = _stream(cfg, spec, 3)
    oracle = sa_check.KmerOracle(reads, spec["k"], sa_check.stride_bits(200))
    errors = sum(int((stream.read(i) != _clean(stream, i)).sum())
                 for i in range(200))
    assert errors / (200 * 200) == pytest.approx(spec["error_rate"], rel=0.15)
    present = np.mean([(oracle.counts(stream.batch(i)) > 0).mean()
                       for i in range(200)])
    assert present == pytest.approx((1 - spec["error_rate"]) ** spec["k"],
                                    abs=0.05)
    clean = traffic.QueryStream(stream.genome, 200, dict(spec, error_rate=0),
                                3)
    assert (oracle.counts(clean.batch(7)) > 0).all()


def _clean(stream, i):
    """The genome window that batch ``i``'s read was sequenced from."""
    rng = np.random.default_rng(traffic.derive_seed(stream.seed, 3, i))
    start = int(rng.integers(0, stream.genome.size - stream.read_len))
    return stream.genome[start:start + stream.read_len]
