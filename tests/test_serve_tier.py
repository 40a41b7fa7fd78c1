"""Serve tier: bucketed scheduler, executable cache, batch-level encode.

The PR 8 layered service core (DESIGN.md §14): routing/zero-pad
admission in the scheduler, the compiled-executable cache's no-recompile
invariant under a mixed-bucket workload, the one-container-per-batch
response encode, the retry-exhausted re-queue deadline satellite, and
the progressive fidelity-tier route over stored responses.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import codec
from repro import kernels as K
from repro.resilience import inject
from repro.resilience.errors import DeadlineExceededError, RetryExhaustedError
from repro.serve import (
    BucketScheduler,
    ProgressiveServeRoute,
    TransformRequest,
    WaveletServeEngine,
    crop_result,
    tier_shape,
)


def _image(seed=0, shape=(16, 16)):
    return np.random.default_rng(seed).integers(
        -100, 100, shape, dtype=np.int32
    )


def _pyramids_equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# Scheduler: routing, fairness, validation (no device work).
# ---------------------------------------------------------------------------


def test_scheduler_routes_smallest_containing_bucket():
    sched = BucketScheduler([(64, 64), (16, 16), (32, 32)])
    assert sched.route((16, 16)) == (16, 16)  # exact match
    assert sched.route((17, 8)) == (32, 32)  # smallest that contains
    assert sched.route((16, 33)) == (64, 64)  # one oversize axis reroutes
    with pytest.raises(ValueError, match="bucket"):
        sched.route((65, 2))
    with pytest.raises(ValueError, match="rank"):
        sched.route((16, 16, 16))


def test_scheduler_cross_bucket_fifo_is_oldest_head_first():
    sched = BucketScheduler([(16, 16), (32, 32)])
    a = TransformRequest(uid=1, image=_image(1, (32, 32)))
    b = TransformRequest(uid=2, image=_image(2, (16, 16)))
    sched.submit(a)  # older, in the larger bucket
    sched.submit(b)
    bucket, batch = sched.next_batch(8)
    assert bucket == (32, 32) and [r.uid for r in batch] == [1]
    bucket, batch = sched.next_batch(8)
    assert bucket == (16, 16) and [r.uid for r in batch] == [2]


def test_scheduler_rejects_bad_bucket_sets():
    with pytest.raises(ValueError, match="rank"):
        BucketScheduler([(16, 16), (4, 16, 16)])
    with pytest.raises(ValueError, match="duplicate"):
        BucketScheduler([(16, 16), (16, 16)])
    with pytest.raises(ValueError, match="max_queue"):
        BucketScheduler([(16, 16)], max_queue=0)


# ---------------------------------------------------------------------------
# Engine: zero-pad admission, multi-bucket serving.
# ---------------------------------------------------------------------------


def test_zero_pad_admission_reconstructs_bit_exactly():
    """An undersized request rides a larger bucket zero-padded; inverse
    transform + crop recovers the original samples bit-exactly."""
    eng = WaveletServeEngine(buckets=[(16, 16)], batch_slots=2, levels=2)
    req = TransformRequest(uid=1, image=_image(3, (13, 11)))
    eng.submit(req)
    (done,) = eng.step()
    assert done.done and done.padded and done.bucket == (16, 16)
    assert done.pyramid.ll.shape == (4, 4)  # bucket-shaped pyramid
    back = K.dwt_inv_2d_multi(done.pyramid)
    np.testing.assert_array_equal(crop_result(back, req), req.image)


def test_multi_bucket_engine_serves_mixed_shapes():
    eng = WaveletServeEngine(
        buckets=[(16, 16), (8, 8)], batch_slots=4, levels=1
    )
    reqs = [
        TransformRequest(uid=i, image=_image(i, shape))
        for i, shape in enumerate([(8, 8), (16, 16), (5, 7), (11, 16)])
    ]
    done = eng.run(list(reqs))
    assert sorted(r.uid for r in done) == [0, 1, 2, 3]
    by_uid = {r.uid: r for r in done}
    assert by_uid[0].bucket == (8, 8) and by_uid[2].bucket == (8, 8)
    assert by_uid[1].bucket == (16, 16) and by_uid[3].bucket == (16, 16)
    for r in done:
        back = K.dwt_inv_2d_multi(r.pyramid)
        np.testing.assert_array_equal(crop_result(back, r), r.image)


def test_engine_rejects_buckets_plus_legacy_shape():
    with pytest.raises(ValueError, match="not both"):
        WaveletServeEngine(height=16, width=16, buckets=[(16, 16)])
    with pytest.raises(ValueError, match="buckets"):
        WaveletServeEngine()


def test_engine_rejects_float_samples():
    eng = WaveletServeEngine(buckets=[(16, 16)], levels=1)
    with pytest.raises(TypeError, match="integer"):
        eng.submit(TransformRequest(uid=1, image=np.zeros((16, 16), np.float32)))


# ---------------------------------------------------------------------------
# Executor: the no-recompile invariant.
# ---------------------------------------------------------------------------


def test_executable_cache_compiles_once_per_bucket():
    """A mixed-bucket workload must build exactly one executable per
    bucket and then run at a 100% hit rate — admissions, bucket
    switches, drained-and-refilled queues never recompile.  ``traces``
    counts actual retraces of the cached jit's Python body (under jit
    the body runs only while tracing), so a cache that silently rebuilt
    would show traces > misses."""
    eng = WaveletServeEngine(
        buckets=[(16, 16), (32, 32)], batch_slots=2, levels=1
    )
    assert eng.warmup() == 2
    hits0, misses0 = eng.executor.hits, eng.executor.misses
    for round_ in range(3):  # interleave buckets across rounds
        reqs = [
            TransformRequest(
                uid=10 * round_ + i,
                image=_image(i + round_, (16, 16) if i % 2 else (32, 32)),
            )
            for i in range(4)
        ]
        done = eng.run(reqs)
        assert len(done) == 4
    assert misses0 == 2 and hits0 == 0  # warmup paid both compiles
    assert eng.executor.misses == 2  # nothing recompiled since
    assert eng.executor.hits == 6  # 2 micro-batches x 3 rounds, all hits
    assert eng.executor.traces == 2, "cached executable retraced"


def test_warmup_compiles_every_bucket_before_traffic():
    """Warm-up traces and compiles each bucket's executable from its
    static batch shape, so serving the first requests traces nothing."""
    eng = WaveletServeEngine(
        buckets=[(16, 16), (32, 32)], batch_slots=2, levels=1
    )
    eng.warmup()
    assert eng.executor.traces == 2
    done = eng.run([
        TransformRequest(uid=i, image=_image(i, (16, 16) if i % 2 else (32, 32)))
        for i in range(4)
    ])
    assert all(r.done for r in done)
    assert eng.executor.traces == 2, "a request paid a trace after warm-up"


def test_executor_key_isolation():
    """Distinct (scheme, levels) settings get distinct executables; the
    same key built twice is a cache bug, not a new compile."""
    from repro.serve import ExecKey, TransformExecutor

    ex = TransformExecutor()
    k1 = ExecKey((16, 16), 2, "cdf53", 1, "paper", None, None)
    k2 = ExecKey((16, 16), 2, "haar", 1, "paper", None, None)
    f1 = ex.executable(k1)
    assert ex.executable(k1) is f1  # hit returns the same callable
    assert ex.executable(k2) is not f1
    assert (ex.hits, ex.misses, ex.compiles) == (1, 2, 2)


# ---------------------------------------------------------------------------
# Batch-level response encode.
# ---------------------------------------------------------------------------


def test_batch_encode_shares_one_container():
    """encode_response serves ONE WZRC container per micro-batch: every
    request carries the same bytes plus its row index, and decode_batch
    returns each request's pyramid bit-exactly."""
    eng = WaveletServeEngine(
        buckets=[(16, 16)], batch_slots=4, levels=1, encode_response=True
    )
    reqs = [TransformRequest(uid=i, image=_image(i)) for i in range(3)]
    done = eng.run(list(reqs))
    blobs = {id(r.encoded) for r in done}
    assert len(blobs) == 1  # literally the same container object
    assert sorted(r.batch_index for r in done) == [0, 1, 2]
    rows = codec.decode_batch(done[0].encoded)
    assert len(rows) == 3
    for r in done:
        assert _pyramids_equal(rows[r.batch_index], r.pyramid)


def test_batch_container_excludes_empty_slots():
    """A partially-filled micro-batch encodes only its live rows — the
    zero-filled padding slots never ship."""
    eng = WaveletServeEngine(
        buckets=[(16, 16)], batch_slots=8, levels=1, encode_response=True
    )
    eng.submit(TransformRequest(uid=1, image=_image(1)))
    eng.submit(TransformRequest(uid=2, image=_image(2)))
    done = eng.step()
    assert len(codec.decode_batch(done[0].encoded)) == 2


def test_batch_encode_failure_degrades_to_single_request_containers():
    eng = WaveletServeEngine(
        buckets=[(16, 16)], batch_slots=2, levels=1, encode_response=True
    )
    eng.submit(TransformRequest(uid=1, image=_image(1)))
    eng.submit(TransformRequest(uid=2, image=_image(2)))
    with inject.armed("serve.encode_batch", times=1):
        done = eng.step()
    for r in done:
        assert r.error is None and r.encoded is not None
        assert r.batch_index is None  # per-request containers
        dec = codec.decode_pyramid(r.encoded)
        assert _pyramids_equal(dec.pyramid, r.pyramid)


# ---------------------------------------------------------------------------
# Retry-exhausted re-queue honors deadlines (satellite).
# ---------------------------------------------------------------------------


def test_requeued_batch_expires_overdue_requests():
    """A batch that burns its retry budget re-queues WITHOUT the
    requests whose deadline passed during the failed attempts: they come
    back with a typed DeadlineExceededError on the next step, never
    silently served late."""
    eng = WaveletServeEngine(
        buckets=[(16, 16)],
        batch_slots=2,
        levels=1,
        deadline_s=0.02,
        max_retries=1,
        retry_backoff_s=0.05,  # one backoff sleep > the deadline
    )
    req = TransformRequest(uid=1, image=_image(1))
    eng.submit(req)
    with inject.armed("serve.transform", times=None):
        with pytest.warns(Warning, match="retrying"):
            with pytest.raises(RetryExhaustedError):
                eng.step()
    # the fault is gone, but the request went overdue while it retried
    (back,) = eng.step()
    assert back is req and not back.done
    assert isinstance(back.error, DeadlineExceededError)
    assert eng.scheduler.pending() == 0  # nothing left queued


def test_requeued_batch_keeps_live_requests():
    """Without a deadline, retry exhaustion loses nothing: the batch
    re-queues at the head and serves once the fault clears."""
    eng = WaveletServeEngine(
        buckets=[(16, 16)], batch_slots=2, levels=1,
        max_retries=0, retry_backoff_s=0.001,
    )
    eng.submit(TransformRequest(uid=1, image=_image(1)))
    with inject.armed("serve.transform", times=None):
        with pytest.raises(RetryExhaustedError):
            eng.step()
    (done,) = eng.step()
    assert done.done and done.error is None


# ---------------------------------------------------------------------------
# Progressive fidelity-tier route.
# ---------------------------------------------------------------------------


def test_route_thumbnail_and_refine_from_one_stored_blob():
    eng = WaveletServeEngine(
        buckets=[(16, 16)], batch_slots=4, levels=2, encode_response=True
    )
    reqs = [TransformRequest(uid=i, image=_image(i)) for i in range(3)]
    done = eng.run(list(reqs))
    route = ProgressiveServeRoute()
    for r in done:
        route.store(r)
    r0 = next(r for r in done if r.uid == 0)
    thumb = route.thumbnail(0)
    np.testing.assert_array_equal(thumb, np.asarray(r0.pyramid.ll))
    assert route.tiers(0) == {0: (4, 4), 1: (8, 8), 2: (16, 16)}
    mid = route.refine(0, 1)
    assert mid.shape == (8, 8)
    np.testing.assert_array_equal(route.full(0), r0.image)


def test_route_crops_padded_requests_per_tier():
    eng = WaveletServeEngine(
        buckets=[(16, 16)], batch_slots=2, levels=2, encode_response=True
    )
    req = TransformRequest(uid=7, image=_image(7, (13, 10)))
    (done,) = eng.run([req])
    route = ProgressiveServeRoute()
    route.store(done)
    assert tier_shape((13, 10), 2, 0) == (4, 3)
    assert route.thumbnail(7).shape == (4, 3)
    assert route.refine(7, 1).shape == (7, 5)
    np.testing.assert_array_equal(route.full(7), req.image)


def test_route_requires_encoded_response():
    route = ProgressiveServeRoute()
    with pytest.raises(ValueError, match="no encoded response"):
        route.store(TransformRequest(uid=1, image=_image(1)))
    with pytest.raises(KeyError, match="no stored response"):
        route.thumbnail(99)
