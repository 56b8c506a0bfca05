"""The one traffic generator: every input of a run is drawn from ``--seed``
and from the parameters in the cell's configuration and traffic files.

Read sets are the grouper shape: reads sampled from one synthetic genome
at a fixed coverage, so that suffixes overlap as in sequencing data.  The
sampler is the program's ``repro.data.corpus.synth_dna_reads`` (same draws
for the same integer seed), kept here so that no change to the program
moves the benchmark's inputs.

A traffic file is JSON.  Its ``kind`` names the driver in ``drivers/``;
the remaining keys are that driver's parameters, read here:

* ``build``: back-to-back index builds, each of a read set of its own at
  the configuration's size.
* ``lookup``: each batch is the ``k``-token seed at every offset of one
  read of the configuration's length, sequenced afresh from the indexed
  genome with a substitution at each base with probability
  ``error_rate`` (a different base, uniformly).  A seed is present in
  the index when its bases are error-free, absent as a rule otherwise.
"""
from __future__ import annotations

import numpy as np

DNA_VOCAB = 4


def derive_seed(seed: int, *tags: int) -> int:
    """A 63-bit seed for one stream of a run, from the run's seed (any
    whole number, negative or above 2**32 included) and the stream's tags."""
    ss = np.random.SeedSequence([seed % (1 << 64), *tags])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(1))


def dna_genome_reads(num_reads: int, read_len: int, seed: int,
                     coverage: int = 16):
    """``(genome, reads)``: one random genome of ``num_reads * read_len /
    coverage`` bases (A=1 C=2 G=3 T=4) and ``(num_reads, read_len)`` int32
    reads sampled from it."""
    rng = np.random.default_rng(seed)
    g = max(4 * read_len, num_reads * read_len // coverage)
    genome = rng.integers(1, DNA_VOCAB + 1, size=(g,)).astype(np.int32)
    starts = rng.integers(0, g - read_len, size=(num_reads,))
    return genome, genome[starts[:, None] + np.arange(read_len)[None, :]]


def dna_reads(num_reads: int, read_len: int, seed: int,
              coverage: int = 16) -> np.ndarray:
    """The reads of :func:`dna_genome_reads`."""
    return dna_genome_reads(num_reads, read_len, seed, coverage)[1]


def job_genome_reads(config: dict, seed: int, job: int,
                     warmup: bool = False):
    """The genome and read set of job ``job`` of a run (of its set-up when
    ``warmup``), at the configuration's size and coverage."""
    return dna_genome_reads(config["reads"], config["read_len"],
                            derive_seed(seed, 4 if warmup else 1, job),
                            config["coverage"])


def job_reads(config: dict, seed: int, job: int,
              warmup: bool = False) -> np.ndarray:
    """The read set of job ``job`` (see :func:`job_genome_reads`)."""
    return job_genome_reads(config, seed, job, warmup)[1]


class QueryStream:
    """Batches of lookup seeds; batch ``i`` depends only on the seed and
    ``i``, so every run of a seed sends the same batches in the same
    order, and every batch has the same number of seeds."""

    def __init__(self, genome: np.ndarray, read_len: int, traffic: dict,
                 seed: int):
        self.genome = genome
        self.read_len = read_len
        self.k = int(traffic["k"])
        self.error_rate = float(traffic["error_rate"])
        self.seed = seed

    def read(self, i: int, warmup: bool = False) -> np.ndarray:
        """The sequenced read of batch ``i``: a genome window, at a start
        drawn as the index's reads draw theirs, with its errors."""
        rng = np.random.default_rng(
            derive_seed(self.seed, 5 if warmup else 3, i))
        start = int(rng.integers(0, self.genome.size - self.read_len))
        read = self.genome[start:start + self.read_len].astype(np.int64)
        err = rng.random(self.read_len) < self.error_rate
        shift = rng.integers(1, DNA_VOCAB, size=self.read_len)
        read[err] = (read[err] - 1 + shift[err]) % DNA_VOCAB + 1
        return read

    def batch(self, i: int, warmup: bool = False) -> np.ndarray:
        """``(read_len - k + 1, k)`` int64 seeds of batch ``i`` (of the
        set-up's batches when ``warmup``), in read order."""
        return np.lib.stride_tricks.sliding_window_view(
            self.read(i, warmup), self.k).copy()
