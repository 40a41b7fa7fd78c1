"""95th percentile (nearest rank) of the scheduler's queue wait over the
window's requests: admission to the ``next_batch`` that took the request,
as the ``queue_wait_ms`` of the window's ``serve.step`` roots.  The
reader of ``sched_wait_p95_ms.open``."""
from bench import spans, work


def read(run):
    w = spans.window("serve.step", run.obs_delta.get("serve.batches", 0))
    if not w:
        return None
    return work.p95([ms for r in w.roots for ms in r.args.get("queue_wait_ms", ())])
