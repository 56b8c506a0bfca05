"""``pattern_cmp_roofline``: the least time the chip's HBM bandwidth
allows for the bytes every ``pattern_cmp`` call of the traced window must
move (from the call shapes, ``bench/kernel_cost.py``), over the kernel's
device time in the trace.  Memory bounds it: the kernel does a few integer
operations per byte."""
from bench import kernel_cost


def read(run):
    calls = [shape for name, shape in run.kernel_calls
             if name == "pattern_cmp"]
    if run.trace is None or not calls:
        return None
    t = run.trace.kernel_s("pattern_cmp")
    if not t:
        return None
    nbytes = sum(kernel_cost.pattern_cmp_bytes(*shape) for shape in calls)
    least = nbytes / kernel_cost.peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / t
