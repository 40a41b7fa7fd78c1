"""Wavelet transform serving engine — the layered service core.

The image/tensor-compression workload of the paper's modules, served
batched at hardware speed.  PR 8 split the old single-dataclass engine
into three layers (DESIGN.md §14):

    scheduler.py   multi-bucket FIFO admission: nearest-bucket routing
                   with zero-pad admission, load shedding, deadlines
    executor.py    compiled-executable cache keyed on
                   (bucket, scheme, levels, mode, backend, mesh) with
                   donated input buffers — no admission or bucket
                   switch ever recompiles
    engine.py      this module: micro-batch assembly, bounded retry,
                   batch-level response encode (ONE WZRC container per
                   micro-batch, lead dim = batch), and the progressive
                   fidelity-tier route (serve/routes.py)

Requests of ANY shape a registered bucket contains are admitted: the
batch row is zero-padded to the bucket, the transform stays static
shaped (one executable per bucket), and the response records the
original shape so clients crop after inverse transform — padding is
outside the data, so reconstruction stays bit-exact.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import ranges as _ranges
from repro.resilience import inject
from repro.resilience.errors import (
    ResilienceWarning,
    RetryExhaustedError,
    RetryWarning,
)
from repro.serve.executor import ExecKey, TransformExecutor, mesh_signature
from repro.serve.scheduler import BucketScheduler

Shape = Tuple[int, ...]


@dataclass
class TransformRequest:
    uid: int
    image: np.ndarray  # integer samples; any shape a registered bucket contains
    pyramid: Optional[Any] = None  # Pyramid2D/PyramidND result (when served)
    encoded: Optional[bytes] = None  # WZRC container (encoded-response route)
    batch_index: Optional[int] = None  # row in the batch container (None =
    # single-request container: decode with codec.decode_pyramid directly)
    bucket: Optional[Shape] = None  # the bucket this request rode (scheduler)
    done: bool = False
    submitted_at: Optional[float] = None  # monotonic clock, set by submit()
    error: Optional[Exception] = None  # per-request failure (deadline, encode)

    @property
    def padded(self) -> bool:
        """True when the request rode a bucket larger than its image."""
        return self.bucket is not None and tuple(self.image.shape) != self.bucket


@dataclass
class WaveletServeEngine:
    """Continuous micro-batched 2D/3D DWT serving over shape buckets.

    ``buckets`` registers the served shape set — e.g.
    ``buckets=[(256, 256), (512, 512)]`` — each with its own FIFO queue
    and its own cached executable; a request routes to the smallest
    bucket containing its shape and is zero-padded up to it.  The
    legacy single-bucket constructor (``height=``/``width=`` and
    optionally ``depth=``) still works and is equivalent to registering
    that one bucket.

    ``depth``-style 3D buckets — ``buckets=[(4, 64, 64), ...]`` — serve
    (D, H, W) volumes through the fused N-D engine (kernels/fused3d.py);
    2D buckets serve through the fused 2D pyramid, or the row-sharded
    ``shard_map`` transform when ``mesh`` is set (2D-only, every bucket
    validated against the mesh at construction).

    ``encode_response=True`` makes the engine an end-to-end lossless
    codec service.  PR 8 moved the encode to the batch level: each
    micro-batch ships as ONE self-describing WZRC container whose lead
    dim is the batch (``codec.encode_batch``), so the host-side coder
    runs once per dispatch instead of once per request.  Every request
    in the batch carries the same container bytes plus its
    ``batch_index``; clients take their row with ``codec.decode_batch``
    (or any fidelity tier of it via ``codec.progressive`` — thumbnails
    and refinements decode from byte ranges of the same stored blob).
    If the batch-level encode fails, the engine degrades to the PR 6
    per-request encode loop so one poisoned request quarantines alone.

    Overload and failure semantics are PR 6's, now enforced by the
    scheduler/executor layers (DESIGN.md §12, §14):

      * admission control — ``submit`` raises
        :class:`~repro.resilience.errors.LoadShedError` once the total
        queue (across buckets) holds ``max_queue`` requests;
      * per-request deadlines — with ``deadline_s`` set, an overdue
        request is dropped from the batch it would have ridden in and
        comes back with ``error`` set to
        :class:`~repro.resilience.errors.DeadlineExceededError`.  The
        deadline is re-checked on the retry-exhausted re-queue path, so
        a batch that burned through its retry budget can never serve
        requests that went overdue while it was failing;
      * bounded retry — a transform failure retries up to
        ``max_retries`` times with exponential backoff
        (:class:`~repro.resilience.errors.RetryWarning` per attempt);
        exhaustion re-queues the still-live requests (none lost) and
        raises :class:`~repro.resilience.errors.RetryExhaustedError`;
      * encode degradation — a response-encode failure attaches the
        error to the affected request(s) only; the pyramid still serves;
      * range certification — with ``checked=True`` (or
        ``REPRO_DWT_CHECKED``), ``submit`` traces the request's sample
        interval through the cascade and sheds wrap-capable requests
        with a typed :class:`~repro.resilience.errors.IntegerOverflowError`.
    """

    height: Optional[int] = None
    width: Optional[int] = None
    depth: Optional[int] = None  # legacy single (D, H, W) volume bucket
    buckets: Optional[Sequence[Sequence[int]]] = None
    batch_slots: int = 8
    levels: int = 2
    mode: str = "paper"
    scheme: str = "cdf53"  # lifting scheme from the registry
    backend: Optional[str] = None
    encode_response: bool = False  # attach WZRC bytes to served requests
    mesh: Optional[Any] = None  # jax.sharding.Mesh -> sharded transform
    mesh_axis: str = "data"
    max_queue: int = 1024  # admission budget: submit() sheds beyond this
    deadline_s: Optional[float] = None  # per-request deadline (from submit)
    max_retries: int = 2  # transform retries after the first attempt
    retry_backoff_s: float = 0.05  # backoff base: 1x, 2x, 4x, ...
    checked: Optional[bool] = None  # range-certify at submit (None: env)
    executor: TransformExecutor = field(default_factory=TransformExecutor)

    def __post_init__(self):
        from repro.core import lifting as _lifting
        from repro.core import schemes as _schemes

        if self.batch_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got {self.batch_slots}")
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        _schemes.get_scheme(self.scheme)  # fail fast on unknown names

        if self.buckets is not None:
            if self.height is not None or self.width is not None or self.depth is not None:
                raise ValueError(
                    "pass either buckets= or the legacy height/width[/depth], "
                    "not both"
                )
            bucket_list = [tuple(int(s) for s in b) for b in self.buckets]
        else:
            if self.height is None or self.width is None:
                raise ValueError(
                    "register buckets= or the legacy height=/width= pair"
                )
            if self.depth is not None:
                bucket_list = [(self.depth, self.height, self.width)]
            else:
                bucket_list = [(self.height, self.width)]

        for b in bucket_list:
            if len(b) == 3:
                _lifting.check_levels_nd(b, self.levels)
                if self.mesh is not None:
                    raise ValueError(
                        "the sharded mesh route is 2D-only; volume buckets "
                        "(depth set) serve through the fused N-D engine"
                    )
            else:
                _lifting.check_levels_2d(b[0], b[1], self.levels)
            if self.mesh is not None:
                from repro.kernels import sharded as _sharded

                _sharded.check_shardable(
                    b[0], b[1], self.mesh.shape[self.mesh_axis],
                    self.levels, self.scheme,
                )

        # max_queue < 1 is the scheduler's error; keep its message shape
        self.scheduler = BucketScheduler(
            bucket_list, max_queue=self.max_queue, deadline_s=self.deadline_s
        )
        self._mesh_sig = mesh_signature(self.mesh)
        # requests that went overdue on the retry-exhausted re-queue
        # path; delivered (with their typed error) by the next step()
        self._expired_out: List[TransformRequest] = []

    # -- introspection ------------------------------------------------------

    @property
    def bucket_shape(self) -> Shape:
        """The single registered bucket (legacy engines).

        Multi-bucket engines have no single shape — use
        ``scheduler.buckets``.
        """
        if len(self.scheduler.buckets) != 1:
            raise ValueError(
                f"engine serves {len(self.scheduler.buckets)} buckets "
                f"({list(self.scheduler.buckets)}); bucket_shape is "
                "single-bucket-only"
            )
        return self.scheduler.buckets[0]

    def _exec_key(self, bucket: Shape) -> ExecKey:
        return ExecKey(
            bucket=bucket,
            batch_slots=self.batch_slots,
            scheme=self.scheme,
            levels=self.levels,
            mode=self.mode,
            backend=self.backend,
            mesh_axes=self._mesh_sig,
        )

    def warmup(self) -> int:
        """Pre-compile every bucket's executable; returns how many built."""
        return self.executor.warmup(
            (self._exec_key(b) for b in self.scheduler.buckets), self.mesh
        )

    # -- admission ----------------------------------------------------------

    def submit(self, req: TransformRequest) -> None:
        if not np.issubdtype(req.image.dtype, np.integer):
            raise TypeError(
                "integer DWT serving requires integer samples, got "
                f"{req.image.dtype}; quantize client-side "
                "(core.compression.quantize) before submitting"
            )
        bucket = self.scheduler.route(req.image.shape)  # ValueError if none
        if _ranges.checked_enabled(self.checked) and req.image.size:
            # admission-time range certification: reject a request whose
            # samples could wrap a lifting intermediate BEFORE it rides a
            # batch (one host min/max + a cascade trace, no device work)
            _ranges.assert_interval_safe(
                int(req.image.min()),
                int(req.image.max()),
                scheme=self.scheme,
                levels=self.levels,
                dtype=np.int32,  # step() batches every bucket as int32
                mode=self.mode,
                ndim=len(bucket),
                label=f"serve.submit(request {req.uid})",
            )
        self.scheduler.submit(req)  # sheds (LoadShedError) past max_queue

    # -- execution ----------------------------------------------------------

    def _transform_with_retry(self, batch_np: np.ndarray, key: ExecKey):
        """Bounded-backoff retry around the batched transform.

        The device array is rebuilt from the host batch per attempt: the
        executor donates input buffers on accelerators, so an array that
        rode a failed attempt must never be resubmitted.
        """
        attempts = self.max_retries + 1
        # fetching the executable compiles it on a cache miss; a lowering
        # or compile failure is deterministic, so it propagates from here
        # on the first attempt instead of being retried as transient
        fn = self.executor.executable(key, self.mesh)
        for attempt in range(attempts):
            try:
                inject.check("serve.transform")
                out = self.executor.run(fn, jnp.asarray(batch_np), key)
            except Exception as e:  # noqa: BLE001 - transient device faults
                if attempt + 1 >= attempts:
                    obs.counter("serve.retries_exhausted").inc()
                    obs.emit(obs.FaultEvent(
                        subsystem="serve", error=type(e).__name__,
                        site="serve.transform",
                    ))
                    raise RetryExhaustedError(
                        f"transform failed after {attempts} attempts: "
                        f"{type(e).__name__}: {e}"
                    ) from e
                obs.counter("serve.retry_attempts").inc()
                # RetryWarning (same category/stacklevel as the old direct
                # warn) + a RetryEvent per attempt — the warning keeps CI's
                # -W error::RuntimeWarning behaviour, the event keeps count
                obs.warn_event(
                    obs.RetryEvent(
                        subsystem="serve", attempt=attempt + 1,
                        attempts=attempts, error=type(e).__name__,
                    ),
                    RetryWarning(
                        f"transform attempt {attempt + 1}/{attempts} failed "
                        f"({type(e).__name__}: {e}); retrying"
                    ),
                    stacklevel=3,
                )
                time.sleep(self.retry_backoff_s * (2 ** attempt))
            else:
                if attempt:
                    obs.emit(obs.HealEvent(
                        subsystem="serve", mechanism="retry",
                        detail=f"succeeded on attempt {attempt + 1}/{attempts}",
                    ))
                return out

    def _encode_batch(self, active: List[TransformRequest], pyr) -> None:
        """Batch-level response encode: ONE WZRC container per micro-batch.

        The container's lead dim is the (active) batch, so the host-side
        Rice coder runs once per dispatch.  Failure degrades to the
        per-request encode loop — a poisoned request quarantines alone,
        everyone else still gets bytes.
        """
        from repro.codec import container

        nd = 3 if len(active[0].bucket) == 3 else None
        n = len(active)
        try:
            inject.check("serve.encode_batch")
            sliced = jax.tree_util.tree_map(lambda b: b[:n], pyr)
            blob = container.encode_batch(
                sliced, scheme=self.scheme, mode=self.mode, ndim=nd,
                backend=self.backend,
            )
        except Exception as e:  # noqa: BLE001 - degrade to per-request
            obs.counter("serve.encode_degrades").inc()
            obs.warn_event(
                obs.DegradeEvent(
                    subsystem="serve", requested="batch-encode",
                    resolved="per-request-encode",
                    reason=f"{type(e).__name__}: {e}",
                ),
                ResilienceWarning(
                    f"batch-level response encode failed "
                    f"({type(e).__name__}: {e}); degrading to per-request "
                    "encode"
                ),
                stacklevel=3,
            )
        else:
            for i, r in enumerate(active):
                r.encoded = blob
                r.batch_index = i
            return
        for r in active:
            try:
                inject.check("serve.encode")
                r.encoded = container.encode_pyramid(
                    r.pyramid, scheme=self.scheme, mode=self.mode, ndim=nd,
                    backend=self.backend,
                )
                r.batch_index = None
            except Exception as e:  # noqa: BLE001 - quarantine per request
                r.error = e
                obs.counter("serve.encode_quarantines").inc()
                obs.warn_event(
                    obs.FaultEvent(
                        subsystem="serve", error=type(e).__name__,
                        site="serve.encode",
                        detail=f"request {r.uid} quarantined",
                    ),
                    ResilienceWarning(
                        f"response encode failed for request {r.uid} "
                        f"({type(e).__name__}: {e}); serving the "
                        "pyramid without its encoded bytes"
                    ),
                    stacklevel=3,
                )

    def step(self) -> List[TransformRequest]:
        """Serve one micro-batch; returns the requests it completed.

        Deadline-missed requests come back alongside the served ones,
        with ``done=False`` and ``error`` set — check per request.
        """
        overdue = self._expired_out + self.scheduler.expire_overdue()
        self._expired_out = []
        bucket, active = self.scheduler.next_batch(self.batch_slots)
        if bucket is None:
            return overdue
        now = time.monotonic()
        bucket_label = "x".join(str(s) for s in bucket)
        # the root span of a served batch: its subtree splits the step into
        # batch assembly, transform dispatch, per-request slicing and the
        # response encode (whose device waits carry ``wait_s``)
        with obs.span(
            "serve.step", subsystem="serve", bucket=bucket_label, n=len(active),
            uids=[r.uid for r in active],
            queue_wait_ms=[(now - (r.submitted_at or now)) * 1e3 for r in active],
        ):
            t0 = time.perf_counter()
            # static batch shape: the executable is compiled for
            # (batch_slots,) + bucket, so unfilled slots — and the padding
            # margin of undersized requests — are ZERO-filled (zeros ride
            # the transform and are discarded; they never repeat live data)
            with obs.span("serve.assemble", subsystem="serve"):
                batch = np.zeros((self.batch_slots,) + bucket, np.int32)
                for i, r in enumerate(active):
                    batch[(i,) + tuple(slice(0, s) for s in r.image.shape)] = r.image
            key = self._exec_key(bucket)
            try:
                pyr = self._transform_with_retry(batch, key)
            except Exception:
                # no live request is lost (retry exhaustion or a compile
                # failure): the batch goes back to its queue head while the
                # error reaches the caller.  Requests whose deadline passed
                # DURING the failed attempts are expired here — a re-queued
                # batch must not serve already-overdue work — and delivered
                # (typed error attached) by the next step()
                expired, live = self.scheduler.expire_batch(active)
                self._expired_out.extend(expired)
                self.scheduler.requeue_front(bucket, live)
                raise
            with obs.span("serve.slice", subsystem="serve"):
                for i, r in enumerate(active):
                    r.pyramid = jax.tree_util.tree_map(lambda b, i=i: b[i], pyr)
            if self.encode_response and active:
                self._encode_batch(active, pyr)
            for r in active:
                r.done = True
            obs.histogram("serve.batch_latency_ms", bucket=bucket_label).observe(
                (time.perf_counter() - t0) * 1e3
            )
            obs.counter("serve.requests_served").inc(len(active))
            obs.counter("serve.batches").inc()
        return overdue + active

    def run(self, requests: List[TransformRequest]) -> List[TransformRequest]:
        for r in requests:
            self.submit(r)
        done: List[TransformRequest] = []
        while self.scheduler.pending() or self._expired_out:
            done.extend(self.step())
        return done


def crop_result(arr: np.ndarray, req: TransformRequest) -> np.ndarray:
    """Crop a reconstructed bucket-shaped sample array back to the
    request's original shape (the zero-pad admission inverse)."""
    return np.asarray(arr)[tuple(slice(0, s) for s in req.image.shape)]
