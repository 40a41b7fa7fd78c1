"""Rice coder chunks dispatched per request served: the ``chunks`` of
every ``codec.encode_band`` span under the window's ``serve.step`` roots,
over the requests those roots served.  The reader of
``coder_chunks_per_request.bulk``."""
from bench import spans


def read(run):
    w = spans.window("serve.step", run.obs_delta.get("serve.batches", 0))
    if not w:
        return None
    served = sum(len(r.args.get("uids", ())) for r in w.roots)
    return spans.chunks(w, "codec.encode_band") / served if served else None
