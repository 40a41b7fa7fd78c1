"""Ingest: least bytes of the pipeline (samples in at 2 B, coded bytes
out) at peak HBM bandwidth, over device busy time in the window, in %.
The reader of ``codec_roofline.bulk`` and ``codec_roofline.open``."""
from bench import work


def read(run):
    done = run.traced
    least = work.ingest_bytes(sum(r.samples for r in done), work.coded_bytes(done))
    return work.roofline_pct(least, run)
