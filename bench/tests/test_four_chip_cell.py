"""The four-chip build cell: its files load as one cell, its shuffle
readers read the program's counters and the trace, and read nothing from
a program or a run that lacks them."""
import os

import pytest

from bench.harness import BENCH, Cell, Run, load_module
from bench.trace import Summary

CELL = "grouper-reads-4chip.build"
APPENDED = ["build_suffixes_per_s", "device_idle_share.build",
            "pipeline.refine_iters", "store.fetch_requests_per_suffix",
            "publish.host_share"]


def reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py")).read


def test_cell_loads_on_four_chips():
    cell = Cell(CELL)
    assert cell.chips == 4
    assert cell.config["reads"] == 32000 and cell.config["read_len"] == 200
    assert cell.config["sa_config"]["adaptive"] is False
    assert "shuffle_slack" not in cell.config["sa_config"]
    assert cell.traffic["kind"] == "build"
    assert [m["name"] for m in cell.metrics(traced=False)] == [
        "build_suffixes_per_s", "setup_s"]
    traced = {m["name"] for m in cell.metrics(traced=True)}
    assert traced == set(APPENDED[1:]) | {"shuffle.pad_factor",
                                          "shuffle.collective_share"}


def build_run(*stats):
    return Run(cell=CELL, window_s=56.0, jobs=[
        {"suffixes": 6_432_000, "wall_s": 28.0, "stats": dict(s)}
        for s in stats])


def test_pad_factor():
    read = reader("shuffle.pad_factor")
    fits = {"shuffle_slots": 4 * 4 * 804_000, "shuffle_records": 6_432_000}
    rebuilt = {"shuffle_slots": 4 * 4 * (804_000 + 500_000),
               "shuffle_records": 6_432_000}
    assert read(build_run(fits, fits)) == 2.0
    assert read(build_run(fits, rebuilt)) == pytest.approx(
        (2 * 804_000 + 500_000) * 16 / (2 * 6_432_000))
    assert read(build_run({"iters": 8, "t_device_s": 9.0})) is None
    assert read(build_run()) is None


def test_collective_share():
    read = reader("shuffle.collective_share")
    run = build_run()
    run.trace = Summary(window_s=56.0, busy_s=24.0, collective_s=0.03)
    assert read(run) == pytest.approx(0.125)
    run.trace = Summary(window_s=56.0, busy_s=24.0)
    assert read(run) == 0.0  # one chip: no collective in the trace
    run.trace = Summary(window_s=56.0, busy_s=0.0)
    assert read(run) is None
    assert read(build_run()) is None  # untraced


def test_small_run_is_correct(cpu_run):
    res = cpu_run(CELL)
    assert res["correct"], res
    assert res["attempted"] > 0 and res["failed"] == 0
