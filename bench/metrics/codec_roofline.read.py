"""Reads: least bytes of the pipeline (the slice's share of coded bytes
in, samples out at 2 B) at peak HBM bandwidth, over device busy time, in %."""
from bench import work


def read(run):
    done = run.traced
    per = run.extra.get("coded_bytes_per_slice", {})
    coded_in = sum(per.get(r.pool_index, 0.0) for r in done)
    return work.roofline_pct(work.read_bytes(coded_in, sum(r.samples for r in done)), run)
