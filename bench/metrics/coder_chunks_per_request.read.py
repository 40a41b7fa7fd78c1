"""Rice coder chunks dispatched per read: the ``chunks`` of every
``codec.decode_band`` span under the window's ``serve.read`` roots, over
the reads."""
from bench import spans


def read(run):
    w = spans.window("serve.read", len(run.records))
    return spans.chunks(w, "codec.decode_band") / len(w.roots) if w else None
