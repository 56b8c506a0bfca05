"""``device_idle_share.lookup``: share of the traced window in which no
operation ran on the device, averaged over the cell's chips."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
