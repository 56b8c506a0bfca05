"""``build_suffixes_per_s``: suffixes of every build the window completed,
over the window's whole length (host clock)."""


def read(run):
    if not run.jobs:
        return None
    return sum(j["suffixes"] for j in run.jobs) / run.window_s
