"""``serve.compare_rounds_per_batch``: batched compare rounds the engine ran
(``engine_stats()["compare_rounds"]``) per window batch."""


def read(run):
    if not run.latencies_s:
        return None
    return run.engine["compare_rounds"] / len(run.latencies_s)
