"""``lookup_queries_per_s``: queries answered (count and locate) in the
window, over the window's whole length (host clock)."""


def read(run):
    if not run.latencies_s:
        return None
    return run.queries / run.window_s
