#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, as the one process that uses the chips.
The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number with its limit.
The run exits non-zero and prints no result when JAX finds no TPU, or
fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout root (for ``bench.*``) and the system under test; not this
# directory, whose ``trace.py`` would shadow the standard library's
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
# libtpu's logs go inside the checkout, not to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, ".bench_work", "tpu_logs"))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
