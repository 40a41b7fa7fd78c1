"""The traffic generator: turns a mix's parameters and a seed into work.

A mix file (``traffic/<mix>.json``) names its ``pattern``
(``patterns/<pattern>.py``, which drives the window) and the parameters
read here:

- ``closed``: a standing backlog of ``backlog`` queued requests.
  Requests cycle through the pool, each cycle in a seeded order, so every
  seed ingests the same mix of shapes (:func:`pool_order`).
- ``open``: single arrivals at ``rate_per_s`` (:func:`arrivals`).  The
  gaps are the quantiles of an exponential distribution at that rate, in
  an order drawn from the mix's own ``schedule_seed``: every run replays
  the same arrival times, so a tail latency measures the system and not
  the luck of the draw.  The run's seed picks the images each arrival
  brings.
- ``read``: one closed-loop reader of the ingested series, taking slices
  in a seeded uniform order, with replacement (:func:`read_order`);
  ``trace_seconds`` bounds the traced part of a traced run's window.
"""
from __future__ import annotations

import math
from typing import Dict, Iterator, List, Tuple

import numpy as np

from bench.data import rng_for


def pool_order(pool_size: int, seed: int) -> Iterator[int]:
    """Pool indices forever: each pass over the pool in a seeded order."""
    rng = rng_for(seed, 2)
    while True:
        yield from (int(i) for i in rng.permutation(pool_size))


def arrivals(mix: Dict, pool_size: int, seed: int, seconds: float) -> List[Tuple[float, int]]:
    """``(due_s, pool_index)`` of every open-loop request due in ``seconds``."""
    rate = float(mix["rate_per_s"])
    n = max(1, math.ceil(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate  # exponential quantiles, mean 1/rate
    gaps = gaps[rng_for(int(mix["schedule_seed"]), 3).permutation(n)]
    due = np.cumsum(gaps) - gaps[0]  # the first arrival is due at 0
    images = pool_order(pool_size, seed)
    return [(float(t), next(images)) for t in due[due < seconds]]


def read_order(n_slices: int, seed: int) -> Iterator[int]:
    """Series slice indices forever, uniform with replacement."""
    rng = rng_for(seed, 4)
    while True:
        yield from (int(i) for i in rng.integers(0, n_slices, 4096))
