"""Driver of the ``build`` traffic: index builds back to back.

Each job builds a fresh read set of the configuration's size through
``SuffixArrayIndex.build(reads, cfg=, sb=, index_dir=, mesh=)``, which
publishes the index (SA, LCP, corpus, manifest) in a directory of its own.
The window starts no job after its deadline and ends when the last job it
started has published, so it holds whole builds only.

``correct``: after the window, every job's index is read back from its
directory and checked against the raw tokens by the plain reference: the
SA is a permutation of all suffixes in the reference's order, and the LCP
equals the reference's count.  Both comparisons are exact.
"""
from __future__ import annotations

import os
import shutil
import time

import numpy as np

from bench import traffic
from bench.harness import span
from bench.reference import sa_check

# published layout of an index directory (repro.core.index_io)
SA_FILE, LCP_FILE = "suffix_array.npy", "lcp.npy"
LIMITS = {"sa_defects": 0, "lcp_defects": 0}  # exact


class Driver:
    def __init__(self, cell, seed: int, mesh, workdir: str, run):
        from repro.config import SAConfig, SuperblockConfig

        self.config = cell.config
        self.seed = seed
        self.mesh = mesh
        self.workdir = workdir
        self.run = run
        self.cfg = SAConfig(**self.config["sa_config"])
        sb = self.config.get("superblock")
        self.sb = SuperblockConfig(**sb) if sb else None

    def _build(self, reads, name: str) -> dict:
        from repro.serve.sa_engine import SuffixArrayIndex

        index_dir = os.path.join(self.workdir, name)
        t0 = time.perf_counter()
        with span("build"):
            idx = SuffixArrayIndex.build(reads, cfg=self.cfg, sb=self.sb,
                                         index_dir=index_dir, mesh=self.mesh)
        stats = dict(idx.build_stats)
        idx.close()
        return {"dir": index_dir, "suffixes": reads.shape[0]
                * (reads.shape[1] + 1), "wall_s": time.perf_counter() - t0,
                "stats": stats}

    def setup(self) -> dict:
        t0 = time.perf_counter()
        reads = traffic.job_reads(self.config, self.seed, 0, warmup=True)
        t1 = time.perf_counter()
        # one whole build of the window's shape: every program it runs is
        # compiled (or loaded from the cache) here, not in the window
        job = self._build(reads, "warmup")
        shutil.rmtree(job["dir"], ignore_errors=True)
        return {"corpus_s": t1 - t0, "warmup_s": time.perf_counter() - t1}

    def window(self, deadline: float) -> None:
        j = 0
        while True:
            reads = traffic.job_reads(self.config, self.seed, j)
            self.run.jobs.append(self._build(reads, f"job{j}"))
            j += 1
            if time.perf_counter() >= deadline:
                return

    def check(self):
        sums = dict.fromkeys(LIMITS, 0)
        failed = 0
        for j, job in enumerate(self.run.jobs):
            reads = traffic.job_reads(self.config, self.seed, j)
            sa = np.load(os.path.join(job["dir"], SA_FILE))
            lcp = np.load(os.path.join(job["dir"], LCP_FILE))
            d = sa_check.index_defects(sa, lcp, reads)
            shutil.rmtree(job["dir"], ignore_errors=True)
            sums["sa_defects"] += d["sa"]
            sums["lcp_defects"] += d["lcp"]
            failed += int(d["sa"] > 0 or d["lcp"] > 0)
        checks = {k: (v, LIMITS[k]) for k, v in sums.items()}
        return checks, len(self.run.jobs), failed

    def close(self) -> None:
        pass
