"""``publish.host_share``: share of the window's build time spent
publishing the index on the host: build stats ``t_lcp_s`` (the LCP of a
single-pass build), ``t_save_s`` (the index written, checksummed and
renamed in place) and ``t_open_s`` (the index reopened), summed over the
window's builds, over the sum of their walls."""

KEYS = ("t_lcp_s", "t_save_s", "t_open_s")


def read(run):
    jobs = [j for j in run.jobs if "t_save_s" in j["stats"]]
    if not jobs:
        return None
    return (100.0 * sum(j["stats"].get(k, 0.0) for j in jobs for k in KEYS)
            / sum(j["wall_s"] for j in jobs))
