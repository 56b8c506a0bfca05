"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on one TPU v5e chip (three
lookup batches through ``pattern_cmp`` and one 8,000-read build, inside a
``window`` span)."""
import gzip
import os
from types import SimpleNamespace

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "v5e_lookup_build.xplane.pb.gz")


def ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def test_union():
    got = trace.union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)])
    assert got == [(0, 3), (5, 9), (10, 11)]


def test_names():
    assert trace.op_name("%while.7 = (s32[4]{0}) while(%t), body=%b") == \
        "while.7"
    assert trace.module_name("jit_pattern_cmp(9557026942066183522)") == \
        "jit_pattern_cmp"


def test_host_labels_take_outer_span_and_inner_event():
    events = [ev("window", 0, 100), ev("build", 10, 50),
              ev("PjitFunction(f)", 20, 5), ev("count", 70, 20)]
    assert trace._host_labels(events, [5, 22, 40, 75, 95]) == [
        "(no host event)", "build > PjitFunction(f)", "build", "count",
        "(no host event)"]


@pytest.fixture(scope="module")
def data():
    import jax

    with open(DATA, "rb") as f:
        return jax.profiler.ProfileData.from_serialized_xspace(
            gzip.decompress(f.read()))


@pytest.fixture(scope="module")
def summary(data):
    return trace.reduce_data(data, 1)


def test_recorded_trace_busy_and_idle(summary):
    assert 0 < summary.busy_s < summary.window_s
    assert 0 < summary.idle_share < 1
    idle = sum(s for _, s in summary.gaps)
    assert idle + summary.busy_s == pytest.approx(summary.window_s, rel=1e-6)
    # self times split the busy time: nested ops are not counted twice
    assert sum(summary.ops.values()) == pytest.approx(summary.busy_s,
                                                      rel=1e-3)
    assert min(summary.ops.values()) >= 0
    assert summary.collective_s == 0  # one chip
    # the build's refinement loop holds most of the device time
    top = max(summary.ops, key=summary.ops.get)
    assert top.startswith("jit__device_fn:")


def test_recorded_trace_names_kernel_and_host_spans(summary):
    k = summary.kernel_s("pattern_cmp")
    # its own op only, not the copies that take its output
    assert k == sum(t for op, t in summary.ops.items()
                    if op.split(":")[1].startswith("pattern_cmp."))
    assert 0 < k < 1e-3 * 3 * 42
    labels = {n.split(" > ")[0] for n, _ in summary.gaps}
    assert {"count", "locate", "build"} <= labels
    bd = summary.breakdown()
    assert len(bd["device_ops"]) == trace.TOP
    assert bd["device_ops"][0][1] >= bd["device_ops"][-1][1]
    assert 0 < len(bd["idle_gaps"]) <= trace.TOP


def test_recorded_trace_has_no_second_device(data):
    with pytest.raises(ValueError, match="device planes"):
        trace.reduce_data(data, 2)
