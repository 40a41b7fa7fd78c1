"""Container bytes x 8 over the request samples they carry, for the
requests completed in the window: what a lossless archive stores."""
from bench import work


def read(run):
    done = run.completed
    samples = sum(r.samples for r in done)
    return 8.0 * work.coded_bytes(done) / samples if samples else None
