"""The readers of the program's span timers, on made-up runs: each reads
its number from the build stats, and reads nothing from a program that does
not report them."""
import os

import pytest

from bench.harness import BENCH, Run, load_module


def reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py")).read


def build_run(*stats, wall_s=20.0):
    return Run(cell="build", window_s=60.0, jobs=[
        {"suffixes": 1000, "wall_s": wall_s, "stats": dict(s)}
        for s in stats])


def test_publish_host_share():
    read = reader("publish.host_share")
    single = {"t_lcp_s": 2.0, "t_save_s": 3.0, "t_open_s": 1.0}
    ooc = {"t_save_s": 1.0, "t_open_s": 1.0, "t_merge_s": 12.0}
    assert read(build_run(single, single)) == pytest.approx(30.0)
    assert read(build_run(ooc)) == pytest.approx(10.0)  # no LCP pass
    assert read(build_run({"t_merge_s": 12.0})) is None
    assert read(build_run()) is None


def test_merge_fetch_share():
    read = reader("merge.fetch_share")
    st = {"t_merge_s": 10.0, "t_merge_refill_s": 2.0,
          "t_merge_escalate_s": 1.0, "t_merge_rank_s": 3.0,
          "t_merge_collect_s": 0.5, "t_merge_emit_s": 2.5, "t_fetch_s": 3.5}
    assert read(build_run(st, st)) == pytest.approx(35.0)
    assert read(build_run({"t_merge_s": 10.0})) is None  # no fetch timer
    assert read(build_run({"iters": 8})) is None  # single pass: no merge
