"""``store.fetch_requests_per_suffix``: window fetch requests the device
pipeline sent to the store (build stats ``fetch_requests``), per suffix
sorted, over the window's single-pass builds."""


def read(run):
    jobs = [j for j in run.jobs if "fetch_requests" in j["stats"]]
    if not jobs:
        return None
    return (sum(j["stats"]["fetch_requests"] for j in jobs)
            / sum(j["suffixes"] for j in jobs))
