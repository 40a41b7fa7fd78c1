"""One run of one cell: set-up, the measured window, and the check.

The configuration names the system under test (``systems/<name>.py``)
and the mix names the pattern that drives it (``patterns/<name>.py``);
this module holds what they share.  A pattern drives the system only
through its entry points: ``WaveletServeEngine.submit``/``step`` with
``encode_response=True`` in the ingest patterns, and
``ProgressiveServeRoute.full`` in the read pattern.  It keeps its own
timestamps (host clock, ``time.perf_counter``) and writes a
``jax.profiler.TraceAnnotation`` around each call it makes, so a traced
run can charge the device's idle gaps to what the host was doing.

Nothing in the window decides whether the output is correct beyond
collecting it: :func:`check` holds the window's answers to the plain
reference (``reference.py``) once the window has closed.
"""
from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Set

import numpy as np

from bench import data, reference, registry

CLOCK = time.perf_counter
# bucket samples of the batch containers an ingest check decodes, beyond
# one container for each request shape (the reference decodes about
# 5 million a second on one host core)
CHECK_BUCKET_SAMPLES = 32 * 2**20


@contextlib.contextmanager
def annotate(name: str):
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@dataclass
class Record:
    """One request of the window, as the harness saw it."""

    uid: int
    pool_index: int
    shape: tuple
    due: float  # seconds from the window's start
    submitted: float = float("nan")
    started: float = float("nan")  # the step (or read) that served it began
    finished: float = float("nan")
    error: Optional[str] = None
    blob: Optional[bytes] = None  # ingest: the container the engine returned
    batch_index: Optional[int] = None
    delivered: Optional[np.ndarray] = None  # read: the samples full() returned

    @property
    def samples(self) -> int:
        return int(np.prod(self.shape))

    @property
    def answered(self) -> bool:
        if self.error is not None or np.isnan(self.finished):
            return False
        return self.blob is not None or self.delivered is not None


@dataclass
class Run:
    """What a run measured, handed to every metric reader."""

    cell: str
    config: Dict
    mix: Dict
    seed: int
    seconds: float
    control: str = "none"
    root: Path = registry.ROOT  # where configs, mixes, patterns and readers are found
    setup_s: float = 0.0
    setup_parts: Dict[str, float] = field(default_factory=dict)
    window_s: float = 0.0
    records: List[Record] = field(default_factory=list)
    obs_delta: Dict[str, float] = field(default_factory=dict)
    window_compiles: int = 0
    trace: Optional[Dict] = None
    peaks: Optional[Dict] = None
    extra: Dict[str, Any] = field(default_factory=dict)
    stop_trace: Optional[Callable[[], None]] = None  # set where the run is traced
    traced_until: Optional[float] = None  # where the trace covers part of the window

    @property
    def completed(self) -> List[Record]:
        return [r for r in self.records if r.answered]

    @property
    def traced(self) -> List[Record]:
        """The completed requests the trace covers."""
        if self.traced_until is None:
            return self.completed
        return [r for r in self.completed if r.finished <= self.traced_until]


def system_image(img: np.ndarray, control: str) -> np.ndarray:
    """What the system is given for a pool image.

    ``control="lsb"`` is the control of the lossless guarantee: the
    samples at one bit less than the configuration stores, so a run that
    is correct against the pool image cannot come out of it.
    """
    if control == "none":
        return img
    if control == "lsb":
        return img & np.int16(-2)
    raise ValueError(f"unknown control {control!r}")


def ingest_setup(run: Run, engine, pool: List[np.ndarray]):
    """Set-up of an ingest pattern: compile every shape the window uses,
    the transform and a batch at each occupancy from full down to one
    request (the coder's chunk shapes and the engine's per-row slicing
    depend on it).  Returns the state its window drives."""
    from repro.serve import TransformRequest

    t = CLOCK()
    engine.warmup()
    uid = -1
    for n in range(engine.batch_slots, 0, -1):
        reqs = []
        for i in range(n):
            img = system_image(pool[(-uid + i) % len(pool)], run.control)
            reqs.append(TransformRequest(uid=uid, image=img))
            uid -= 1
        engine.run(reqs)
    run.setup_parts["warmup_s"] = CLOCK() - t
    return engine, pool


def finish(done, records: Dict[int, Record], started: float, t0: float) -> float:
    """Stamp the requests a step returned (seconds from ``t0``) and drop
    their device pyramids; returns the clock at the stamp."""
    t = CLOCK()
    for req in done:
        rec = records.get(req.uid)
        req.pyramid = None  # the window keeps no device state per request
        if rec is None:
            continue
        rec.started, rec.finished = started - t0, t - t0
        rec.blob, rec.batch_index = req.encoded, req.batch_index
        if req.error is not None or not req.done:
            rec.error = type(req.error).__name__ if req.error else "not done"
    return t


# ---------------------------------------------------------------------------
# The check, after the window has closed.
# ---------------------------------------------------------------------------


def containers_to_check(run: Run) -> Set[int]:
    """A sample, drawn from the seed, of the batch containers the window's
    answered ingest requests came in (by ``id`` of their bytes): first a
    container for each request shape not yet covered, padded ones
    included, then more until they hold ``CHECK_BUCKET_SAMPLES``."""
    rows: Dict[int, set] = {}
    for rec in run.records:
        if rec.answered and rec.blob is not None:
            rows.setdefault(id(rec.blob), set()).add(rec.shape)
    blobs = list(rows)
    order = [blobs[i] for i in data.rng_for(run.seed, 5).permutation(len(blobs))]
    bucket = max(math.prod(b) for b in run.config["buckets"]) * run.config["batch_slots"]
    picked: Set[int] = set()
    shapes: set = set()
    for b in order:
        if rows[b] - shapes:
            picked.add(b)
            shapes |= rows[b]
    for b in order:
        if len(picked) * bucket >= CHECK_BUCKET_SAMPLES:
            break
        picked.add(b)
    return picked


def check(run: Run, pool: List[np.ndarray]) -> Dict[str, Dict[str, int]]:
    """The window's answers against the plain reference.

    Every request due in the window must be answered.  Ingest: the
    container of each sampled request (:func:`containers_to_check`),
    decoded by ``reference.py`` a group of rows at a time, must hold its
    pool image in its row, an image or a volume alike.
    Read: each delivered slice must be the series slice it names.
    Returns each compared number with its limit.
    """
    kw = {k: run.config[k] for k in ("levels", "mode", "scheme")}
    sampled = containers_to_check(run)
    wrong = unanswered = checked = 0
    rows: Dict[int, tuple] = {}  # a sampled container and its (row, image) pairs
    for rec in run.records:
        img = pool[rec.pool_index]
        if not rec.answered:
            unanswered += 1
        elif rec.delivered is not None:
            wrong += reference.mismatches(np.asarray(rec.delivered), None, img)
            checked += 1
        elif id(rec.blob) in sampled:
            rows.setdefault(id(rec.blob), (rec.blob, []))[1].append((rec.batch_index, img))
            checked += 1
    for blob, pairs in rows.values():  # a batch container decodes once
        wrong += reference.container_mismatches(blob, pairs, **kw)
    return {
        "mismatched_samples": {"value": wrong, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "checked": {"value": checked, "limit": 1},
    }


def passed(checks: Dict[str, Dict[str, int]]) -> bool:
    """``checked`` is a floor (something was compared); the rest are caps."""
    return all(
        c["value"] >= c["limit"] if name == "checked" else c["value"] <= c["limit"]
        for name, c in checks.items()
    )


def setup_and_window(run: Run, window_start: Callable[[], None],
                     window_end: Callable[[], None]) -> List[np.ndarray]:
    """Build the pool and the system, set up the mix's pattern, run its
    window.

    Returns the pool the window's requests name (the check's ground
    truth).  ``window_start`` / ``window_end`` bracket the window: the run
    starts and stops the profiler and the compile counter there.
    """
    t = CLOCK()
    pool = data.make_pool(run.config, run.seed, run.root)
    run.setup_parts["pool_s"] = CLOCK() - t
    system = registry.system(run.config["system"], run.root).build(run.config)
    pattern = registry.pattern(run.mix["pattern"], run.root)
    state = pattern.setup(run, system, pool)
    window_start()
    pattern.window(run, state)
    window_end()
    return pool
