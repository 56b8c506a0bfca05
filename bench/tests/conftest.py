"""Helpers of the benchmark's own tests (``python -m pytest bench/tests``):
they run on CPU JAX at tiny sizes, with the harness's look for a chip
skipped."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {"reads": 120, "read_len": 40}


def small_cell(name: str):
    """The cell as its files give it, at a size a test run holds: 120
    reads of 40 tokens, and superblocks of 40 reads where it has them."""
    from bench.harness import Cell

    cell = Cell(name)
    cell.config.update(SMALL)
    if cell.config.get("superblock"):
        cell.config["superblock"]["max_records_per_run"] = 40 * 41
    return cell


@pytest.fixture(scope="session")
def meter():
    from bench.harness import Meter

    return Meter()


@pytest.fixture
def cpu_run(meter):
    """``cpu_run(name, seed)``: one whole run of a small cell on one CPU
    device, as ``bench/run.py`` would make it on a chip."""
    import jax

    from bench.harness import run_cell

    def go(name: str, seed: int = 2**31 + 11, seconds: float = 1.0):
        return run_cell(small_cell(name), seed, seconds, False,
                        jax.devices("cpu")[:1], time.perf_counter(), meter,
                        log=lambda s: None)

    return go
