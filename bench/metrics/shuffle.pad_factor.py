"""``shuffle.pad_factor``: record slots the builds' ``all_to_all`` moved
(build stats ``shuffle_slots``: devices x devices x capacity, over every
pipeline run of a build) over the valid records among them (build stats
``shuffle_records``), summed over the window's builds.  1 is a shuffle
with no padding; a rebuild after an overflow adds its slots too."""


def read(run):
    jobs = [j for j in run.jobs if "shuffle_slots" in j["stats"]]
    records = sum(j["stats"]["shuffle_records"] for j in jobs)
    if not records:
        return None
    return sum(j["stats"]["shuffle_slots"] for j in jobs) / records
