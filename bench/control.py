#!/usr/bin/env python3
"""The controls of the cells' ``correct``: the plain reference put in the
program's place with one guarantee of the configuration broken, its
output handed to the cell's driver and judged by the driver's own
``check()``, as a run's is.  Each has to come out not correct.

    python bench/control.py --workload <cell> --seed <n> [--jobs J] [--batches B]

* ``build`` cells: each job's index published as the SA sorted on the
  first K = 26 tokens only (one key of two words, no tie refinement: the
  step that would save the pipeline's refinement rounds), ties by global
  index, with the LCP the reference counts for that order; ``--jobs``
  read sets, as a window builds.
* ``lookup`` cells: every query answered by its first 13 tokens only (one
  31-bit key word of the two a 19-mer spans); ``--batches`` batches of the
  cell's traffic, as a window sends.

Prints one JSON line: each compared number and its limit.  It needs no
chip; it is kept beside the benchmark and not run by ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

from bench import traffic  # noqa: E402
from bench.harness import BENCH, WORK, Run, load_module  # noqa: E402
from bench.reference import sa_check  # noqa: E402

PREFIX_TOKENS = 26  # K of the configurations: 13 tokens a word, 2 words
WORD_TOKENS = 13


def _driver(cell, seed: int, workdir: str):
    mod = load_module(os.path.join(BENCH, "drivers",
                                   cell.traffic["kind"] + ".py"))
    run = Run(cell=cell.name)
    return mod, mod.Driver(cell, seed, None, workdir, run), run


def build_control(cell, seed: int, jobs: int, workdir: str):
    """The build driver's check of ``jobs`` prefix-sorted indexes."""
    mod, driver, run = _driver(cell, seed, workdir)
    for j in range(jobs):
        reads = traffic.job_reads(cell.config, seed, j)
        sa = sa_check.suffix_array(reads, depth=PREFIX_TOKENS)
        _, lcp = sa_check.adjacent_order(sa, reads,
                                         sa_check.stride_bits(reads.shape[1]))
        d = os.path.join(workdir, f"job{j}")
        os.makedirs(d)
        np.save(os.path.join(d, mod.SA_FILE), sa)
        np.save(os.path.join(d, mod.LCP_FILE), lcp)
        run.jobs.append({"dir": d, "suffixes": sa.size})
    return driver.check()


def lookup_control(cell, seed: int, batches: int, workdir: str):
    """The lookup driver's check of ``batches`` batches answered on one
    key word."""
    _, driver, _ = _driver(cell, seed, workdir)
    driver.inputs()
    one_word = sa_check.KmerOracle(driver.reads, WORD_TOKENS,
                                   sa_check.stride_bits(
                                       driver.reads.shape[1]))
    for i in range(batches):
        pats = driver.stream.batch(i)
        driver.sent.append((pats, one_word.counts(pats),
                            one_word.locate(pats)))
    return driver.check()


def main(argv=None) -> int:
    from bench.harness import Cell

    ap = argparse.ArgumentParser(description="Run one cell's control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--batches", type=int, default=200)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    os.makedirs(WORK, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="control_", dir=WORK)
    try:
        if cell.traffic["kind"] == "build":
            checks, attempted, failed = build_control(cell, args.seed,
                                                      args.jobs, workdir)
        else:
            checks, attempted, failed = lookup_control(cell, args.seed,
                                                       args.batches, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "attempted": attempted, "failed": failed,
        "control": {k: {"value": v, "limit": lim}
                    for k, (v, lim) in checks.items()},
        "correct": all(v <= lim for v, lim in checks.values())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
