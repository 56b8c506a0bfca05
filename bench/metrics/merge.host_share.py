"""``merge.host_share``: share of the window's out-of-core build time spent
in the host merge (build stats ``t_merge_s`` over each build's wall)."""


def read(run):
    jobs = [j for j in run.jobs if "t_merge_s" in j["stats"]]
    if not jobs:
        return None
    return (100.0 * sum(j["stats"]["t_merge_s"] for j in jobs)
            / sum(j["wall_s"] for j in jobs))
