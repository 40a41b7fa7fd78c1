"""Host milliseconds per read: the mean over the window's ``serve.read``
roots of the root's duration less every ``wait_s`` (time blocked on the
device) in its subtree, from the program's spans."""
from bench import spans


def read(run):
    w = spans.window("serve.read", len(run.records))
    return spans.host_ms(w) if w else None
