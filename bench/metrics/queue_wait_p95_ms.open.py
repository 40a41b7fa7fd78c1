"""95th percentile of due time to the start of the step that served the
request (the scheduler's queue wait), from the harness's timestamps."""
from bench import work


def read(run):
    return work.p95([(r.started - r.due) * 1e3 for r in run.completed])
