"""The work a run did, counted from shapes and bytes, not from the program.

The roofline of the codec pipeline counts the least bytes any
implementation must move through HBM for the work the window completed,
whatever ops today's code runs:

- ingest: each request's own samples read once at their stored width
  (2 bytes, int16) plus the coded bytes written once;
- read: each slice's share of its container's coded bytes read once plus
  the delivered samples written once at 2 bytes.

Lifting is a few integer shift-adds per sample and Rice coding a few
more, so the pipeline is bound by bytes, not operations: its least time
is bytes over the chip's HBM bandwidth.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

SAMPLE_BYTES = 2  # int16, the stored width of both deployments


def coded_bytes(records) -> int:
    """Bytes of the distinct containers that carried ``records``."""
    seen = {}
    for r in records:
        if r.blob is not None:
            seen[id(r.blob)] = len(r.blob)
    return sum(seen.values())


def ingest_bytes(request_samples: int, coded: int) -> int:
    return request_samples * SAMPLE_BYTES + coded


def read_bytes(coded_in: float, delivered_samples: int) -> float:
    return coded_in + delivered_samples * SAMPLE_BYTES


def roofline_pct(min_bytes: float, run) -> Optional[float]:
    """Least time at peak HBM bandwidth over device busy time, in %."""
    if not run.trace or not run.peaks or run.trace["busy_s"] <= 0 or min_bytes <= 0:
        return None
    least_s = min_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace["busy_s"]


def idle_pct(run) -> Optional[float]:
    if not run.trace or run.trace["window_s"] <= 0 or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def p95(values: List[float]) -> Optional[float]:
    """The 95th percentile (nearest rank, so a tail of failures, counted
    as infinite, shows as infinite and not as an interpolation)."""
    if not values:
        return None
    v = float(np.percentile(np.asarray(values, float), 95, method="higher"))
    return v if np.isfinite(v) else None

