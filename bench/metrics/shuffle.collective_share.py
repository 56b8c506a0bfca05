"""``shuffle.collective_share``: device time of the cross-chip operations
(all-to-all, all-gather, all-reduce, reduce-scatter, collective-permute)
over the device's busy time in the traced window, both averaged over the
cell's chips (``bench/trace.py``)."""


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    return 100.0 * run.trace.collective_s / run.trace.busy_s
