"""Host milliseconds per served batch: the mean over the window's
``serve.step`` roots of the root's duration less every ``wait_s`` (time
blocked on the device) in its subtree, from the program's spans.  The
reader of ``host_ms_per_step.bulk`` and ``host_ms_per_step.open``."""
from bench import spans


def read(run):
    w = spans.window("serve.step", run.obs_delta.get("serve.batches", 0))
    return spans.host_ms(w) if w else None
