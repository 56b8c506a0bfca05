"""``merge.fetch_share``: share of the out-of-core builds' host merge
(build stats ``t_merge_s``) spent in the store's window fetches on the main
thread (build stats ``t_fetch_s``, the ``store.fetch`` spans: refill,
escalation and pair-LCP windows, chunk-cache misses included), summed over
the window's builds."""


def read(run):
    jobs = [j for j in run.jobs
            if "t_fetch_s" in j["stats"] and "t_merge_s" in j["stats"]]
    merge = sum(j["stats"]["t_merge_s"] for j in jobs)
    if not merge:
        return None
    return 100.0 * sum(j["stats"]["t_fetch_s"] for j in jobs) / merge
