"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* Busy time per device: the union of the intervals in which an operation
  of the ``XLA Ops`` line ran on that device, inside the traced window;
  idle share is 1 minus busy over the window, averaged over the devices.
* Op table: device self time (an op's time less that of the ops it
  contains, as a ``while`` contains its body) per ``module:op`` name, the
  op named by its HLO instruction (``%while.7 = ...`` -> ``while.7``) and
  the program by the ``XLA Modules`` event over it; summed over devices.
* Collective time: device self time of cross-chip operations
  (all-to-all, all-gather, all-reduce, reduce-scatter,
  collective-permute), averaged over the devices.
* Idle gaps of the first device, each named by what the host was doing in
  it: the innermost host event on the benchmark's main thread that covers
  the gap's middle, under the outermost benchmark span there.

The window is the benchmark's own host span ``window``; device and host
events of one trace share the profiler's clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"
COLLECTIVE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute)")
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"want one .xplane.pb under {trace_dir}, found "
                         f"{len(paths)}")
    return paths[0]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, merged ``[start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over the devices
    ops: Dict[str, float] = field(default_factory=dict)  # summed over devices
    collective_s: float = 0.0  # averaged over the devices
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, name: str) -> float:
        """Device seconds, summed over the devices, of the operations named
        ``name`` (``name.N`` in any program)."""
        pat = re.compile(rf"(^|:){re.escape(name)}(\.\d+)?$")
        return sum(t for op, t in self.ops.items() if pat.search(op))

    def breakdown(self) -> dict:
        """The ten device operations that took most time, and the ten host
        activities under which the device idled longest (summed per
        name), in seconds."""
        ops = sorted(self.ops.items(), key=lambda x: -x[1])[:TOP]
        idle: Dict[str, float] = {}
        for name, s in self.gaps:
            idle[name] = idle.get(name, 0.0) + s
        gaps = sorted(idle.items(), key=lambda x: -x[1])[:TOP]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[n, t] for n, t in gaps]}


def _window(host_planes) -> Tuple[float, float, list]:
    """The ``window`` span's bounds and the events of its thread."""
    for plane in host_planes:
        for line in plane.lines:
            events = list(line.events)
            for ev in events:
                if ev.name == WINDOW_SPAN:
                    return ev.start_ns, ev.start_ns + ev.duration_ns, events
    raise ValueError(f"no host span {WINDOW_SPAN!r} in the trace")


def _host_labels(events, times: List[float]) -> List[str]:
    """For each of the ascending ``times``: the outermost benchmark span
    and the innermost host event covering it, on one thread whose events
    nest.  One sweep, with a stack of the events open at each time."""
    events = sorted((e for e in events if e.name != WINDOW_SPAN),
                    key=lambda e: (e.start_ns, -e.duration_ns))
    stack: list = []
    labels = []
    i = 0
    for t in times:
        while i < len(events) and events[i].start_ns <= t:
            ev = events[i]
            while stack and stack[-1].start_ns + stack[-1].duration_ns \
                    <= ev.start_ns:
                stack.pop()
            stack.append(ev)
            i += 1
        while stack and stack[-1].start_ns + stack[-1].duration_ns <= t:
            stack.pop()
        if not stack:
            labels.append("(no host event)")
        elif len(stack) == 1:
            labels.append(stack[0].name)
        else:
            labels.append(f"{stack[0].name} > {stack[-1].name}")
    return labels


def op_name(hlo: str) -> str:
    """``%while.7 = (...) while(...)`` -> ``while.7``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def module_name(event: str) -> str:
    """``jit__device_fn(1576680289436752785)`` -> ``jit__device_fn``."""
    return event.split("(", 1)[0]


def _device_ops(plane, lo: float, hi: float):
    """``(intervals, {module:op: self seconds})`` of one device's ops that
    overlap ``[lo, hi)``, clipped to it."""
    ops, modules = [], []
    for line in plane.lines:
        if line.name == OPS_LINE:
            ops = [e for e in line.events
                   if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
        elif line.name == MODULES_LINE:
            modules = sorted(((e.start_ns, e.start_ns + e.duration_ns,
                               module_name(e.name)) for e in line.events))
    ops.sort(key=lambda e: (e.start_ns, -e.duration_ns))
    spans = []
    self_t: Dict[str, float] = {}
    stack: list = []  # (end, key) of the ops open at the current start
    m = 0
    for e in ops:
        s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
        spans.append((s, t))
        while m < len(modules) and modules[m][1] <= e.start_ns:
            m += 1
        mod = (modules[m][2] if m < len(modules)
               and modules[m][0] <= e.start_ns else "?")
        key = f"{mod}:{op_name(e.name)}"
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:  # the enclosing op's self time excludes this one
            self_t[stack[-1][1]] -= (t - s) * 1e-9
        self_t[key] = self_t.get(key, 0.0) + (t - s) * 1e-9
        stack.append((t, key))
    return spans, self_t


def reduce(path: str, devices: int) -> Summary:
    """The summary of the trace at ``path`` over its first ``devices``
    TPU devices."""
    import jax

    return reduce_data(jax.profiler.ProfileData.from_file(path), devices)


def reduce_data(data, devices: int) -> Summary:
    """``reduce`` of a trace already read (``jax.profiler.ProfileData``)."""
    planes = list(data.planes)
    dev_planes = {}
    for p in planes:
        m = DEVICE_PLANE.match(p.name)
        if m and int(m.group(1)) < devices:
            dev_planes[int(m.group(1))] = p
    if len(dev_planes) != devices:
        raise ValueError(f"trace has {len(dev_planes)} of {devices} TPU "
                         "device planes")
    lo, hi, host_events = _window(p for p in planes
                                  if p.name.startswith("/host:"))
    busy = 0.0
    ops: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for d in sorted(dev_planes):
        spans, self_t = _device_ops(dev_planes[d], lo, hi)
        for k, t in self_t.items():
            ops[k] = ops.get(k, 0.0) + t
        merged = union(spans)
        busy += sum(e - s for s, e in merged) * 1e-9
        if d == min(dev_planes):
            edges = [lo] + [x for se in merged for x in se] + [hi]
            idle = [(s, e) for s, e in zip(edges[::2], edges[1::2],
                                           strict=True) if e > s]
            labels = _host_labels(host_events,
                                  [(s + e) / 2 for s, e in idle])
            gaps = [(n, (e - s) * 1e-9)
                    for n, (s, e) in zip(labels, idle, strict=True)]
    coll = sum(t for k, t in ops.items()
               if COLLECTIVE.match(k.split(":", 1)[1]))
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy / devices, ops=ops,
                   collective_s=coll / devices, gaps=gaps)
