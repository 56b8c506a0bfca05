"""Driver of the ``lookup`` traffic: one closed-loop client of a seed
server.

Set-up builds the configuration's read set into an index through
``SuffixArrayIndex.build(index_dir=)`` (the build cells' path), then serves
it as a reopened index: ``SuffixArrayIndex.open(index_dir, ...)`` with the
configuration's ``server`` settings.  In the window the client sends one
batch at a time, ``count`` then ``locate`` on the same patterns, and sends
the next when both answers are back; a batch's latency runs from its
submission to the second answer.

``correct``: after the window, every answer of every batch is compared
with the plain reference's brute-force k-mer scan of the raw reads: the
count, and the ascending list of occurrences.  Both comparisons are exact.
The reference is built in the check, after the window, and its time is
not set-up.
"""
from __future__ import annotations

import os
import shutil
import time

import numpy as np

from bench import traffic
from bench.harness import span
from bench.reference import sa_check

LIMITS = {"count_defects": 0, "locate_defects": 0}  # exact
COUNTERS = ("queries", "search_rounds", "compare_rounds", "cache_hits",
            "cache_misses", "store_requests")


class Driver:
    def __init__(self, cell, seed: int, mesh, workdir: str, run):
        from repro.config import SAConfig

        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.mesh = mesh
        self.workdir = workdir
        self.run = run
        self.cfg = SAConfig(**self.config["sa_config"])
        self.index = None
        self.sent = []  # (patterns, counts, located) of every window batch

    def inputs(self) -> None:
        """The indexed read set and the query stream, from the seed."""
        genome, self.reads = traffic.job_genome_reads(self.config, self.seed,
                                                      0)
        self.stream = traffic.QueryStream(genome, self.config["read_len"],
                                          self.traffic, self.seed)

    def setup(self) -> dict:
        from repro.serve.sa_engine import SuffixArrayIndex

        t0 = time.perf_counter()
        self.inputs()
        t1 = time.perf_counter()
        index_dir = os.path.join(self.workdir, "index")
        with span("build"):
            SuffixArrayIndex.build(self.reads, cfg=self.cfg,
                                   index_dir=index_dir, mesh=self.mesh).close()
        t2 = time.perf_counter()
        srv = self.config["server"]
        with span("open"):
            self.index = SuffixArrayIndex.open(
                index_dir, store_backend=srv["store_backend"],
                cache_budget_bytes=srv["chunk_cache_bytes"],
                verify=srv["verify"], use_pallas=srv["use_pallas"],
                result_cache_bytes=srv["result_cache_bytes"])
            # one batch of the window's shape: the compare kernel compiles
            # (or loads) and the engine's LLCP/RLCP tables are built here
            warm = self.stream.batch(0, warmup=True)
            self.index.count(warm)
            self.index.locate(warm)
        return {"corpus_s": t1 - t0, "index_build_s": t2 - t1,
                "warmup_s": time.perf_counter() - t2}

    def _counters(self) -> dict:
        st = self.index.engine.engine_stats()
        return {k: st[k] for k in COUNTERS}

    def window(self, deadline: float) -> None:
        from repro.kernels import ops

        before = self._counters()
        cmp = ops.pattern_cmp
        if self.run.tracing:
            # the compare kernel's call shapes, for its roofline share
            def recorded(sfx, *args, **kw):
                self.run.kernel_calls.append(("pattern_cmp", np.shape(sfx)))
                return cmp(sfx, *args, **kw)

            ops.pattern_cmp = recorded
        try:
            i = 0
            while True:
                pats = self.stream.batch(i)
                t0 = time.perf_counter()
                with span("count"):
                    counts = self.index.count(pats)
                with span("locate"):
                    located = self.index.locate(pats)
                t1 = time.perf_counter()
                self.run.latencies_s.append(t1 - t0)
                self.sent.append((pats, counts, located))
                i += 1
                if t1 >= deadline:
                    break
        finally:
            ops.pattern_cmp = cmp
        after = self._counters()
        self.run.engine = {k: after[k] - before[k] for k in COUNTERS}
        self.run.queries = sum(p.shape[0] for p, _, _ in self.sent)

    def check(self):
        oracle = sa_check.KmerOracle(self.reads, int(self.traffic["k"]),
                                     sa_check.stride_bits(
                                         self.reads.shape[1]))
        sums = dict.fromkeys(LIMITS, 0)
        failed = 0
        for pats, counts, located in self.sent:
            d = sa_check.lookup_defects(pats, counts, located, oracle)
            sums["count_defects"] += d["count"]
            sums["locate_defects"] += d["locate"]
            failed += d["queries"]
        checks = {k: (v, LIMITS[k]) for k, v in sums.items()}
        return checks, sum(p.shape[0] for p, _, _ in self.sent), failed

    def close(self) -> None:
        if self.index is not None:
            self.index.close()
            self.index = None
        shutil.rmtree(os.path.join(self.workdir, "index"),
                      ignore_errors=True)
