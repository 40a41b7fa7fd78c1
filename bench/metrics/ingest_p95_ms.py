"""95th percentile of due time to coded response over every request due
in the window; a failed or unanswered request counts as infinitely late."""
from bench import work


def read(run):
    lat = [
        (r.finished - r.due) * 1e3 if r.answered else float("inf") for r in run.records
    ]
    return work.p95(lat)
