"""``pipeline.refine_iters``: tie-refinement iterations of the device
pipeline (build stats ``iters``, the most over its devices), averaged over
the window's single-pass builds."""


def read(run):
    its = [j["stats"]["iters"] for j in run.jobs if "iters" in j["stats"]]
    return sum(its) / len(its) if its else None
