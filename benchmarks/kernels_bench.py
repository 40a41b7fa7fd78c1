"""Kernel-engine benchmark: per-level interpret path vs fused compiled path.

This is the regression gate for the PR series' perf north star: the
compiled-by-default dispatch plus the fused multi-level / fused-2D
engines must beat the seed's behaviour (per-level dispatch of Pallas
kernels under ``interpret=True``) on every workload shape.

Emits CSV rows like every other bench module, and ``run_json()`` also
returns a machine-readable payload that ``benchmarks/run.py`` writes to
``BENCH_kernels.json`` so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels as K
from repro.codec import container as codec_container
from repro.core import lifting as lifting_ref
from repro.kernels import backend as B
from repro.kernels import fused2d, fused3d, ops, ref

# workload shapes: big enough to be meaningful, small enough that the
# interpreter baseline keeps CI smoke under a minute
SHAPE_1D = (8, 16384)
LEVELS_1D = 3
SHAPE_2D = (256, 256)

# tiled-engine workloads (compiled paths only — no interpret baseline):
# a multi-megapixel image that exceeds every whole-image VMEM budget, a
# fused pyramid depth, and a batched-throughput case
SHAPE_2D_LARGE = (2048, 2048)
LEVELS_2D = 3
SHAPE_2D_BATCH = (16, 256, 256)

# 3D engine workloads: a volume big enough that the fused-vs-per-axis
# comparison is meaningful, a small per-scheme roundtrip shape, and a
# video-scale shape used only for path planning (plan_3d is trace-free)
SHAPE_3D = (16, 64, 64)
LEVELS_3D = 2
SHAPE_3D_SCHEME = (8, 16, 16)
SHAPE_3D_LARGE = (64, 512, 512)

# codec workloads: a checkpoint-like smooth matrix (low-frequency surface
# + realistic parameter noise) and a pure-noise one — the gate asserts
# wz-rice beats plain zlib (the ckpt "z" codec) on both
SHAPE_CODEC = (256, 192)
LEVELS_CODEC = 2

# serve workloads: a mixed-bucket request stream small enough for CI
# smoke; the batch-encode comparison uses the smaller bucket, where the
# per-call coder overhead the batch container amortizes dominates
SERVE_BUCKETS = ((16, 16), (32, 32))
SERVE_SLOTS = 8
SERVE_REQUESTS = 32
SERVE_LEVELS = 2


def _time_us(fn, *args, iters: int = 5) -> float:
    out = fn(*args)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()  # warm/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def _per_level_interpret_1d(x: jax.Array, levels: int):
    """The seed's hot path: one interpret-mode kernel dispatch per level."""
    s = x
    details = []
    for _ in range(levels):
        s, d = ops.dwt53_fwd_1d(s, backend="interpret")
        details.append(d)
    return s, tuple(details)


def _per_level_interpret_2d(x: jax.Array):
    """The seed's 2D structure: 1D kernel + 4 transposes, interpret mode."""
    s_r, d_r = ops.dwt53_fwd_1d(x, backend="interpret")
    s_rc = jnp.swapaxes(s_r, -1, -2)
    d_rc = jnp.swapaxes(d_r, -1, -2)
    ll_t, lh_t = ops.dwt53_fwd_1d(s_rc, backend="interpret")
    hl_t, hh_t = ops.dwt53_fwd_1d(d_rc, backend="interpret")
    return (
        jnp.swapaxes(ll_t, -1, -2),
        jnp.swapaxes(lh_t, -1, -2),
        jnp.swapaxes(hl_t, -1, -2),
        jnp.swapaxes(hh_t, -1, -2),
    )


def _bit_exact_check(x1d: jax.Array, x2d: jax.Array) -> bool:
    pyr = K.dwt53_fwd(x1d, levels=LEVELS_1D)
    want = ref.dwt53_fwd(x1d, levels=LEVELS_1D)
    ok = bool(np.array_equal(np.asarray(pyr.approx), np.asarray(want.approx)))
    for a, b in zip(pyr.details, want.details):
        ok = ok and bool(np.array_equal(np.asarray(a), np.asarray(b)))
    ok = ok and bool(
        np.array_equal(np.asarray(K.dwt53_inv(pyr)), np.asarray(x1d))
    )
    bands = K.dwt53_fwd_2d(x2d)
    want2 = ref.dwt53_fwd_2d(x2d)
    for name in ("ll", "lh", "hl", "hh"):
        ok = ok and bool(
            np.array_equal(
                np.asarray(getattr(bands, name)), np.asarray(getattr(want2, name))
            )
        )
    ok = ok and bool(
        np.array_equal(np.asarray(K.dwt53_inv_2d(bands)), np.asarray(x2d))
    )
    return ok


def _codec_section(rng) -> dict:
    """Entropy-codec section: losslessness, throughput, ratio vs zlib."""
    from repro.ckpt import checkpoint as ckpt_mod

    # per-scheme lossless roundtrips through the container (1D/2D/3D)
    lossless = {}
    for name in K.available_schemes():
        x1 = jnp.asarray(rng.integers(-4096, 4096, (2, 200)), jnp.int32)
        x2 = jnp.asarray(rng.integers(-4096, 4096, (17, 23)), jnp.int32)
        x3 = jnp.asarray(rng.integers(-4096, 4096, (6, 9, 10)), jnp.int32)
        ok = codec_container.roundtrip_exact(
            K.dwt_fwd(x1, levels=3, scheme=name), scheme=name
        )
        ok = ok and codec_container.roundtrip_exact(
            K.dwt_fwd_2d_multi(x2, levels=2, scheme=name), scheme=name
        )
        ok = ok and codec_container.roundtrip_exact(
            K.dwt_fwd_nd(x3, levels=2, ndim=3, scheme=name), scheme=name
        )
        lossless[name] = bool(ok)

    # throughput on a checkpoint-like int pyramid (warm second run timed)
    yy, xx = np.meshgrid(
        np.linspace(0, 4, SHAPE_CODEC[0]),
        np.linspace(0, 4, SHAPE_CODEC[1]),
        indexing="ij",
    )
    smooth = (
        np.sin(yy) * np.cos(xx) + 0.02 * rng.normal(size=yy.shape)
    ).astype(np.float32)
    noisy = rng.normal(size=SHAPE_CODEC).astype(np.float32)
    q = jnp.asarray(
        np.round(smooth / np.abs(smooth).max() * 32767), jnp.int32
    )
    pyr = K.dwt_fwd_2d_multi(q, levels=LEVELS_CODEC)
    raw_mb = q.size * 4 / 1e6

    def _best_of(fn, n=3):
        # host-side best-of-n (the codec returns bytes, so the
        # jitted-array _time_us helper doesn't apply); warm call first
        fn()
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    blob = codec_container.encode_pyramid(pyr)
    t_enc = _best_of(lambda: codec_container.encode_pyramid(pyr))
    t_dec = _best_of(lambda: codec_container.decode_pyramid(blob))

    # wz-rice vs the plain-zlib ckpt codec on the SAME leaves
    def sizes(arr):
        rice_b, _ = ckpt_mod._encode(arr, "wz-rice", LEVELS_CODEC)
        z_b, _ = ckpt_mod._encode(arr, "z", LEVELS_CODEC)
        return {
            "raw_bytes": int(arr.nbytes),
            "wz_rice_bytes": len(rice_b),
            "zlib_bytes": len(z_b),
            "ratio_vs_zlib": round(len(z_b) / max(len(rice_b), 1), 2),
        }

    return {
        "block": int(codec_container.rice.BLOCK_VALUES),
        "lossless": lossless,
        "encode_mbps": round(raw_mb / t_enc, 1),
        "decode_mbps": round(raw_mb / t_dec, 1),
        "smooth": sizes(smooth),
        "noisy": sizes(noisy),
    }


def _serve_section(rng) -> dict:
    """Serve-tier section: throughput, tail latency, cache and encode
    amortization over a mixed-bucket continuous-batching workload.

    gate.py pins the structural invariants: the executable cache must be
    100% hits after warmup (no admission or bucket switch recompiles),
    the batch-level response encode must beat the per-request loop by
    1.5x+, and the progressive thumbnail tier must read a strict
    fraction of the stored container's bytes."""
    import jax as _jax

    from repro import codec
    from repro.serve import TransformRequest, WaveletServeEngine

    eng = WaveletServeEngine(
        buckets=list(SERVE_BUCKETS),
        batch_slots=SERVE_SLOTS,
        levels=SERVE_LEVELS,
        encode_response=True,
    )
    eng.warmup()

    def make_requests():
        reqs = []
        for i in range(SERVE_REQUESTS):
            bucket = SERVE_BUCKETS[i % len(SERVE_BUCKETS)]
            # odd requests ride undersized (zero-pad admission)
            shape = bucket if i % 4 < 2 else tuple(s - 3 for s in bucket)
            reqs.append(
                TransformRequest(
                    uid=i,
                    image=rng.integers(-4096, 4096, shape).astype(np.int32),
                )
            )
        return reqs

    eng.run(make_requests())  # warm run: pays compiles + coder jit
    hits0, misses0 = eng.executor.hits, eng.executor.misses
    reqs = make_requests()
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    finished = [r for r in done if r.done and r.error is None]
    # p99 latency: submit-to-completion per request, stamped per step
    lat = []
    eng2 = WaveletServeEngine(
        buckets=list(SERVE_BUCKETS),
        batch_slots=SERVE_SLOTS,
        levels=SERVE_LEVELS,
        encode_response=True,
        executor=eng.executor,  # share the warmed cache
    )
    for r in make_requests():
        eng2.submit(r)
    while eng2.scheduler.pending():
        ts = time.perf_counter()
        batch = eng2.step()
        te = time.perf_counter()
        for r in batch:
            if r.done and r.error is None and r.submitted_at is not None:
                lat.append((te - r.submitted_at) * 1e3)
    p99_ms = float(np.percentile(lat, 99)) if lat else 0.0

    # batch-level encode vs the PR 6 per-request loop, same pyramids
    xb = jnp.asarray(
        rng.integers(-4096, 4096, (SERVE_SLOTS,) + SERVE_BUCKETS[0]),
        jnp.int32,
    )
    pyr = K.dwt_fwd_2d_multi(xb, levels=SERVE_LEVELS)
    per_rows = [
        _jax.tree_util.tree_map(lambda b, i=i: b[i], pyr)
        for i in range(SERVE_SLOTS)
    ]

    def _best_of(fn, n=3):
        fn()
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_batch_enc = _best_of(lambda: codec_container.encode_batch(pyr))
    t_per_enc = _best_of(
        lambda: [codec_container.encode_pyramid(r) for r in per_rows]
    )

    # progressive decode: the thumbnail tier's byte footprint on a
    # stored batch container (measured with the counting reader)
    blob = codec_container.encode_batch(pyr)
    reader = codec.CountingReader(blob)
    codec.decode_lowband(reader)
    thumb_fraction = reader.bytes_read / len(blob)

    return {
        "buckets": [list(b) for b in SERVE_BUCKETS],
        "batch_slots": SERVE_SLOTS,
        "levels": SERVE_LEVELS,
        "requests": SERVE_REQUESTS,
        "requests_per_s": round(len(finished) / wall, 1),
        "p99_ms": round(p99_ms, 2),
        "compiles": int(eng.executor.compiles),
        "cache_hit_rate": round(
            (eng.executor.hits - hits0)
            / max((eng.executor.hits - hits0)
                  + (eng.executor.misses - misses0), 1),
            4,
        ),
        "batch_encode_ms": round(t_batch_enc * 1e3, 2),
        "per_request_encode_ms": round(t_per_enc * 1e3, 2),
        "batch_encode_speedup": round(t_per_enc / t_batch_enc, 2),
        "thumbnail_bytes_fraction": round(thumb_fraction, 4),
    }


def _obs_section(rng) -> dict:
    """Observability section: the cost and coverage of ``repro.obs``.

    Three gated measurements (``gate.py check_obs``):

      * ``overhead_x`` — serve throughput with the instrumentation live
        vs under ``obs.disabled()`` (the bare arm), drift-cancelled
        interleaved pairs like the pyramid comparison.  The acceptance
        budget is 1.10x: "cheap enough to leave on" is a gated claim.
      * ``events`` — event counts from ONE seeded chaos run that arms a
        transient serve fault and a persistent kernel fault while
        touching every subsystem: the full taxonomy (dispatch, degrade,
        fault, heal, retry, admission) must light up.
      * ``metric_subsystems`` / ``span_subsystems`` — the coverage the
        registry and tracer report after that run; all five subsystems
        must be present in both.
    """
    import tempfile
    import warnings

    from jax.sharding import Mesh

    from repro import obs
    from repro.ckpt.checkpoint import CheckpointManager
    from repro.kernels import sharded
    from repro.resilience import inject
    from repro.serve import TransformRequest, WaveletServeEngine

    eng = WaveletServeEngine(
        buckets=list(SERVE_BUCKETS),
        batch_slots=SERVE_SLOTS,
        levels=SERVE_LEVELS,
        encode_response=True,
    )
    eng.warmup()

    def make_requests(n=SERVE_REQUESTS):
        return [
            TransformRequest(
                uid=i,
                image=rng.integers(
                    -4096, 4096, SERVE_BUCKETS[i % len(SERVE_BUCKETS)]
                ).astype(np.int32),
            )
            for i in range(n)
        ]

    def run_once():
        eng.run(make_requests())

    def _best_of(fn, n=3):
        fn()
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    # instrumented vs bare: SAME engine, same warmed executable cache —
    # the obs enabled flag is the only difference between the arms.
    # Interleaved pairs with alternating order (the drift-cancelling
    # protocol from the pyramid comparison); each ratio is taken WITHIN
    # a pair and the mean of the middle two is reported.
    run_once()  # warm both arms' code paths
    ratios = []
    for i in range(4):
        if i % 2 == 0:
            t_on = _best_of(run_once)
            with obs.disabled():
                t_off = _best_of(run_once)
        else:
            with obs.disabled():
                t_off = _best_of(run_once)
            t_on = _best_of(run_once)
        ratios.append(t_on / t_off)
    ratios.sort()
    overhead = (ratios[1] + ratios[2]) / 2

    # one seeded chaos run against fresh ledgers.  Dispatch events are
    # emitted once per distinct routing decision, so the dedup set is
    # cleared to make the run self-contained regardless of what earlier
    # bench sections already dispatched.
    obs.reset()
    B._seen_dispatches.clear()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # serve + codec: a transient transform fault -> admission,
        # retry, and heal events; a batch-encode fault -> the degrade
        # event of the per-request encode fallback
        for r in make_requests(SERVE_SLOTS):
            eng.submit(r)
        with inject.armed("serve.transform", times=1), inject.armed(
            "serve.encode_batch", times=1
        ):
            while eng.scheduler.pending():
                eng.step()
        # kernels: a pallas fault -> dispatch and fault events on the
        # armed interpret path (the fault raises: nothing falls back)
        q = jnp.asarray(rng.integers(-4096, 4096, (64, 64)), jnp.int32)
        try:
            with inject.armed("kernels.pallas", times=1):
                K.dwt_fwd_2d_multi(q, levels=1, backend="interpret")
        except inject.InjectedFault:
            pass
        # ckpt: one save/restore roundtrip through the wz-rice codec
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, codec="wz-rice", wavelet_levels=1)
            mgr.save(0, {"w": np.asarray(q)})
            mgr.restore(0)
        # collectives: a watchdogged halo exchange on a 1-device mesh
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        sharded.dwt_fwd_2d_sharded(q, mesh, levels=1, timeout_s=30.0)
    return {
        "overhead_x": round(overhead, 3),
        "events": obs.events.counts(),
        "event_total": int(obs.events.total),
        "metric_subsystems": sorted(obs.subsystems()),
        "span_subsystems": sorted(obs.tracer.subsystems()),
    }


def _trees_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _resilience_section(rng) -> dict:
    """Resilience section: parity cost + per-fault-class recovery outcomes.

    Outcomes in ``recovery`` are measured, not asserted: each fault class
    the bench can exercise cheaply is actually injected here, and the
    string records what happened.  gate.py pins the expected outcome per
    class, so a regression in any degradation path fails the bench gate
    in addition to the chaos test lane.  The two classes that need
    process-level scaffolding (save-crash, stuck-neighbor) carry the
    outcome the chaos suite enforces (tests/test_resilience.py).
    """
    from repro.codec.errors import CodecError
    from repro.resilience import inject

    q = jnp.asarray(rng.integers(-4096, 4096, SHAPE_CODEC), jnp.int32)
    pyr = K.dwt_fwd_2d_multi(q, levels=LEVELS_CODEC)
    plain = codec_container.encode_pyramid(pyr)
    protected = codec_container.encode_pyramid(pyr, parity=True)
    info = codec_container.peek(protected)
    overhead = len(protected) - len(plain)

    # bit-flip: damage one band byte; the XOR parity group must heal it
    # back bit-exactly and record the reconstruction in band_status
    body_off = len(protected) - sum(info["band_bytes"]) - info["parity_bytes"]
    bad = inject.flip_byte(protected, body_off + info["band_bytes"][0] // 2)
    try:
        dec = codec_container.decode_pyramid(bad)
        healed = "reconstructed" in dec.band_status and _trees_equal(
            dec.pyramid, pyr
        )
    except CodecError:
        healed = False

    # truncation: a mid-stream cut must raise a typed codec error —
    # never decode to garbage
    try:
        codec_container.decode_pyramid(plain[: len(plain) // 2])
        truncation = "silent"
    except CodecError:
        truncation = "typed-error"

    # pallas-failure: an armed kernel fault must raise — a silent
    # recompute on the XLA reference would hide the device from every
    # timing
    try:
        with inject.armed("kernels.pallas", times=None):
            K.dwt_fwd_2d_multi(q, levels=LEVELS_CODEC, backend="interpret")
        pallas_failure = "silent"
    except inject.InjectedFault:
        pallas_failure = "typed-error"

    recovery = {
        "bit-flip": "recovered" if healed else "silent",
        "truncation": truncation,
        # atomic tmp-dir commit + LATEST fallback scan; enforced by
        # tests/test_resilience.py::test_save_crash_leaves_previous_intact
        "save-crash": "previous-intact",
        "pallas-failure": pallas_failure,
        # CollectiveTimeoutError / DeadlineExceededError; enforced by the
        # watchdog and serve-deadline chaos tests
        "stuck-neighbor": "typed-error",
        "deadline-miss": "typed-error",
    }
    return {
        "container_bytes": len(plain),
        "parity_overhead_bytes": overhead,
        "parity_overhead_ratio": round(overhead / len(plain), 4),
        "single_band_recovery": bool(healed),
        "recovery": recovery,
    }


def _ranges_section(rng) -> dict:
    """Range-certificate section: derived int32 safe bounds per scheme,
    the checked mode's outcome on a wrap-capable input through every
    engine, a certified round-trip, and the checked-mode cost measured
    both ways (off must be free, on pays the host interval walk).

    gate.py pins all of it: every engine must report ``typed-error``,
    the cdf53 certificate must keep its derived value, and the
    checked-off ratio must stay ~1.0 (a regression here means the
    disabled path started tracing)."""
    import os

    from jax.sharding import Mesh

    from repro.core import ranges
    from repro.kernels import sharded
    from repro.resilience.errors import IntegerOverflowError

    i32 = np.iinfo(np.int32)
    certs = {}
    for name in K.available_schemes():
        c1 = ranges.range_certificate(name, 1, np.int32)
        c2 = ranges.range_certificate(name, 2, np.int32, ndim=2)
        certs[name] = {
            "safe_abs_1d_l1": int(c1.hi),
            "safe_abs_2d_l2": int(c2.hi),
            "growth_bits_1d_l1": round(c1.growth_bits, 2),
            "int16_levels_3d": int(
                ranges.certified_levels(
                    name, np.int32, (-32767, 32767), ndim=3
                )
            ),
        }

    def outcome(fn):
        try:
            fn()
            return "silent"
        except IntegerOverflowError:
            return "typed-error"

    hot1 = jnp.full((2, 64), i32.max, jnp.int32)
    hot2 = jnp.full((2, 32, 32), i32.max, jnp.int32)
    hot3 = jnp.full((8, 8, 8), i32.max, jnp.int32)
    wraparound = {
        "oracle-1d": outcome(
            lambda: lifting_ref.dwt_fwd(hot1, levels=2, checked=True)
        ),
        "fused-1d": outcome(lambda: K.dwt_fwd(hot1, levels=2, checked=True)),
        "fused-2d": outcome(
            lambda: K.dwt_fwd_2d_multi(hot2, levels=2, checked=True)
        ),
        "fused-3d": outcome(
            lambda: K.dwt_fwd_nd(hot3, levels=2, ndim=3, checked=True)
        ),
    }
    # tiled engine: force the planner onto the tile path via its override
    prev_tile = os.environ.get("REPRO_DWT_TILE")
    os.environ["REPRO_DWT_TILE"] = "16"
    try:
        wraparound["tiled-2d"] = outcome(
            lambda: K.dwt_fwd_2d_multi(hot2, levels=2, checked=True)
        )
    finally:
        if prev_tile is None:
            os.environ.pop("REPRO_DWT_TILE", None)
        else:
            os.environ["REPRO_DWT_TILE"] = prev_tile
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    wraparound["sharded-2d"] = outcome(
        lambda: sharded.dwt_fwd_2d_sharded(
            jnp.full((32, 32), i32.max, jnp.int32), mesh, levels=2,
            checked=True,
        )
    )

    # certificate-respecting inputs flow through checked mode bit-exactly
    lim = min(certs["cdf53"]["safe_abs_2d_l2"], 4096)
    ok_img = jnp.asarray(rng.integers(-lim, lim + 1, (2, 64, 64)), jnp.int32)
    p = K.dwt_fwd_2d_multi(ok_img, levels=2, checked=True)
    roundtrip_exact = bool(
        np.array_equal(
            np.asarray(K.dwt_inv_2d_multi(p, checked=True)),
            np.asarray(ok_img),
        )
    )

    # checked-off cost: drift-cancelled interleaved pairs (same protocol
    # as the pyramid comparison) of default-off vs explicit checked=False
    xb = jnp.asarray(rng.integers(-4096, 4096, (256, 256)), jnp.int32)
    base = lambda a: K.dwt_fwd_2d_multi(a, levels=2)  # noqa: E731
    off = lambda a: K.dwt_fwd_2d_multi(a, levels=2, checked=False)  # noqa: E731
    ratios = []
    for i in range(4):
        if i % 2 == 0:
            b = _time_us(base, xb, iters=10)
            o = _time_us(off, xb, iters=10)
        else:
            o = _time_us(off, xb, iters=10)
            b = _time_us(base, xb, iters=10)
        ratios.append(o / b)
    ratios.sort()
    overhead_off = (ratios[1] + ratios[2]) / 2
    t_on = _time_us(
        lambda a: K.dwt_fwd_2d_multi(a, levels=2, checked=True), xb, iters=3
    )
    t_base = _time_us(base, xb, iters=10)
    return {
        "certificates": certs,
        "wraparound": wraparound,
        "roundtrip_exact": roundtrip_exact,
        "overhead_off_x": round(overhead_off, 3),
        "overhead_on_x": round(t_on / t_base, 2),
    }


def run_json() -> Tuple[list, dict]:
    rng = np.random.default_rng(7)
    x1d = jnp.asarray(rng.integers(-4096, 4096, size=SHAPE_1D), jnp.int32)
    x2d = jnp.asarray(rng.integers(-4096, 4096, size=SHAPE_2D), jnp.int32)

    # --- 1D multi-level --------------------------------------------------
    t_interp_1d = _time_us(
        lambda a: _per_level_interpret_1d(a, LEVELS_1D), x1d, iters=3
    )
    t_fused_1d = _time_us(
        lambda a: K.dwt53_fwd(a, levels=LEVELS_1D), x1d, iters=20
    )
    pyr = K.dwt53_fwd(x1d, levels=LEVELS_1D)
    t_fused_inv_1d = _time_us(lambda p: K.dwt53_inv(p), pyr, iters=20)

    # --- 2D --------------------------------------------------------------
    t_interp_2d = _time_us(_per_level_interpret_2d, x2d, iters=3)
    t_fused_2d = _time_us(lambda a: K.dwt53_fwd_2d(a), x2d, iters=20)
    bands = K.dwt53_fwd_2d(x2d)
    t_fused_inv_2d = _time_us(lambda b: K.dwt53_inv_2d(b), bands, iters=20)

    bit_exact = _bit_exact_check(x1d, x2d)

    # --- tiled engine: multi-megapixel 2D (compiled-vs-compiled) ---------
    x_large = jnp.asarray(
        rng.integers(-4096, 4096, size=SHAPE_2D_LARGE), jnp.int32
    )
    plan_large = fused2d.plan_2d(*SHAPE_2D_LARGE)
    t_large_fwd = _time_us(lambda a: K.dwt53_fwd_2d(a), x_large, iters=3)
    bands_large = K.dwt53_fwd_2d(x_large)
    t_large_inv = _time_us(lambda b: K.dwt53_inv_2d(b), bands_large, iters=3)
    large_exact = bool(
        np.array_equal(
            np.asarray(bands_large.hh), np.asarray(ref.dwt53_fwd_2d(x_large).hh)
        )
    ) and bool(
        np.array_equal(np.asarray(K.dwt53_inv_2d(bands_large)), np.asarray(x_large))
    )

    # --- fused multi-level pyramid vs per-level dispatch ------------------
    def per_level_pyramid(a):
        ll = a
        out = []
        for _ in range(LEVELS_2D):
            bands = K.dwt53_fwd_2d(ll)  # one dispatch (+ glue) per level
            ll = bands.ll
            out.append(bands)
        return ll, out

    # interleaved A/B pairs with alternating order: CPU clocks drift
    # monotonically under load on CI boxes, so each ratio is taken WITHIN
    # a pair and the order inside the pair flips every round — the drift
    # bias cancels in the median of per-pair ratios
    fused_pyr = lambda a: K.dwt53_fwd_2d_multi(a, levels=LEVELS_2D)  # noqa: E731
    pairs = []
    for i in range(4):
        if i % 2 == 0:
            p = _time_us(per_level_pyramid, x_large, iters=5)
            f = _time_us(fused_pyr, x_large, iters=5)
        else:
            f = _time_us(fused_pyr, x_large, iters=5)
            p = _time_us(per_level_pyramid, x_large, iters=5)
        pairs.append((p, f))
    t_pyr_per_level = sorted(p for p, _ in pairs)[1]
    t_pyr_fused = sorted(f for _, f in pairs)[1]
    ratios = sorted(p / f for p, f in pairs)
    pyr_speedup = (ratios[1] + ratios[2]) / 2
    pyr = K.dwt53_fwd_2d_multi(x_large, levels=LEVELS_2D)
    pyr_exact = bool(
        np.array_equal(
            np.asarray(K.dwt53_inv_2d_multi(pyr)), np.asarray(x_large)
        )
    )

    # --- batched throughput: batch -> grid cells vs per-image dispatch ---
    xb = jnp.asarray(rng.integers(-4096, 4096, size=SHAPE_2D_BATCH), jnp.int32)
    t_batch_fused = _time_us(
        lambda a: K.dwt53_fwd_2d_multi(a, levels=2), xb, iters=5
    )

    def per_image(a):
        return [K.dwt53_fwd_2d_multi(a[i], levels=2) for i in range(a.shape[0])]

    t_batch_loop = _time_us(per_image, xb, iters=3)
    imgs_per_s = SHAPE_2D_BATCH[0] / (t_batch_fused * 1e-6)

    # --- per-scheme engine rows: Table-2 op ledger + roundtrip timing ----
    # every registered lifting scheme through the fused 1D and 2D engines;
    # the smoke gate asserts multipliers == 0 and bit-exactness per scheme
    x_s = jnp.asarray(rng.integers(-4096, 4096, size=(8, 4096)), jnp.int32)
    img_s = jnp.asarray(rng.integers(-4096, 4096, size=(128, 128)), jnp.int32)
    schemes_payload = {}
    for name in K.available_schemes():
        sch = K.get_scheme(name)
        ledger = sch.pair_op_counts()
        t_s1 = _time_us(
            lambda a, nm=name: K.dwt_fwd(a, levels=3, scheme=nm), x_s, iters=10
        )
        pyr_s = K.dwt_fwd(x_s, levels=3, scheme=name)
        ok = bool(
            np.array_equal(
                np.asarray(K.dwt_inv(pyr_s, scheme=name)), np.asarray(x_s)
            )
        )
        t_s2 = _time_us(
            lambda a, nm=name: K.dwt_fwd_2d(a, scheme=nm), img_s, iters=10
        )
        b_s = K.dwt_fwd_2d(img_s, scheme=name)
        ok = ok and bool(
            np.array_equal(
                np.asarray(K.dwt_inv_2d(b_s, scheme=name)), np.asarray(img_s)
            )
        )
        schemes_payload[name] = {
            "halo": sch.halo,
            "symmetric": sch.symmetric,
            "adders_per_pair": ledger["adders"],
            "shifters_per_pair": ledger["shifters"],
            "multipliers_per_pair": ledger["multipliers"],
            "fwd_1d_us": round(t_s1, 1),
            "fwd_2d_us": round(t_s2, 1),
            "bit_exact": ok,
        }

    # --- fused 3D engine vs per-axis dispatch -----------------------------
    x3 = jnp.asarray(rng.integers(-4096, 4096, size=SHAPE_3D), jnp.int32)

    def per_axis_3d(a):
        """The pre-engine N-D recipe: one jitted 1D dispatch per axis
        with moveaxis relayouts between them, three per level."""
        approx = a
        for _ in range(LEVELS_3D):
            bands = [approx]
            for axis in (-1, -2, -3):
                nxt = []
                for b in bands:
                    m = jnp.moveaxis(b, axis, -1)
                    s, d = ops.dwt_fwd_1d(m)
                    nxt.append(jnp.moveaxis(s, -1, axis))
                    nxt.append(jnp.moveaxis(d, -1, axis))
                bands = nxt
            approx = bands[0]
        return bands

    fused_3d = lambda a: K.dwt_fwd_nd(a, levels=LEVELS_3D, ndim=3)  # noqa: E731
    # interleaved A/B pairs, alternating order: same drift-cancelling
    # protocol as the 2D pyramid comparison above
    pairs_3d = []
    for i in range(4):
        if i % 2 == 0:
            p = _time_us(per_axis_3d, x3, iters=5)
            f = _time_us(fused_3d, x3, iters=5)
        else:
            f = _time_us(fused_3d, x3, iters=5)
            p = _time_us(per_axis_3d, x3, iters=5)
        pairs_3d.append((p, f))
    t_3d_per_axis = sorted(p for p, _ in pairs_3d)[1]
    t_3d_fused = sorted(f for _, f in pairs_3d)[1]
    r3 = sorted(p / f for p, f in pairs_3d)
    speedup_3d = (r3[1] + r3[2]) / 2

    pyr3 = K.dwt_fwd_nd(x3, levels=LEVELS_3D, ndim=3)
    want3 = lifting_ref.dwt_fwd_nd(x3, levels=LEVELS_3D, ndim=3)
    exact_3d = bool(
        np.array_equal(np.asarray(pyr3.approx), np.asarray(want3.approx))
    )
    for lvl_got, lvl_want in zip(pyr3.details, want3.details):
        for bg, bw in zip(lvl_got, lvl_want):
            exact_3d = exact_3d and bool(
                np.array_equal(np.asarray(bg), np.asarray(bw))
            )
    exact_3d = exact_3d and bool(
        np.array_equal(np.asarray(K.dwt_inv_nd(pyr3)), np.asarray(x3))
    )

    # per-scheme 3D roundtrips (the gate asserts bit-exactness for all)
    x3s = jnp.asarray(
        rng.integers(-4096, 4096, size=SHAPE_3D_SCHEME), jnp.int32
    )
    schemes_3d = {}
    for name in K.available_schemes():
        t_s3 = _time_us(
            lambda a, nm=name: K.dwt_fwd_nd(a, levels=2, ndim=3, scheme=nm),
            x3s, iters=10,
        )
        p_s3 = K.dwt_fwd_nd(x3s, levels=2, ndim=3, scheme=name)
        ok3 = bool(
            np.array_equal(
                np.asarray(K.dwt_inv_nd(p_s3, scheme=name)), np.asarray(x3s)
            )
        )
        schemes_3d[name] = {"bit_exact": ok3, "fwd_us": round(t_s3, 1)}

    codec = _codec_section(rng)
    resilience = _resilience_section(rng)
    ranges_sec = _ranges_section(rng)
    serve = _serve_section(rng)
    observability = _obs_section(rng)

    payload = {
        "platform": B.platform(),
        "default_backend": B.default_backend(),
        "bit_exact": bit_exact,
        "1d_multilevel": {
            "shape": list(SHAPE_1D),
            "levels": LEVELS_1D,
            "per_level_interpret_us": round(t_interp_1d, 1),
            "fused_compiled_us": round(t_fused_1d, 1),
            "fused_compiled_inv_us": round(t_fused_inv_1d, 1),
            "speedup_fused_vs_interpret": round(t_interp_1d / t_fused_1d, 2),
        },
        "2d": {
            "shape": list(SHAPE_2D),
            "per_level_interpret_us": round(t_interp_2d, 1),
            "fused_compiled_us": round(t_fused_2d, 1),
            "fused_compiled_inv_us": round(t_fused_inv_2d, 1),
            "speedup_fused_vs_interpret": round(t_interp_2d / t_fused_2d, 2),
        },
        "2d_large": {
            "shape": list(SHAPE_2D_LARGE),
            "plan": plan_large,
            "bit_exact": large_exact,
            "fwd_us": round(t_large_fwd, 1),
            "inv_us": round(t_large_inv, 1),
        },
        "2d_pyramid": {
            "shape": list(SHAPE_2D_LARGE),
            "levels": LEVELS_2D,
            "bit_exact": pyr_exact,
            "per_level_us": round(t_pyr_per_level, 1),
            "fused_us": round(t_pyr_fused, 1),
            "speedup_fused_vs_per_level": round(pyr_speedup, 2),
        },
        "2d_batched": {
            "shape": list(SHAPE_2D_BATCH),
            "levels": 2,
            "fused_us": round(t_batch_fused, 1),
            "per_image_loop_us": round(t_batch_loop, 1),
            "speedup_batched_vs_loop": round(t_batch_loop / t_batch_fused, 2),
            "images_per_s": round(imgs_per_s, 1),
        },
        "schemes": schemes_payload,
        "3d": {
            "shape": list(SHAPE_3D),
            "levels": LEVELS_3D,
            "plan": fused3d.plan_3d(*SHAPE_3D),
            "bit_exact": exact_3d,
            "per_axis_us": round(t_3d_per_axis, 1),
            "fused_us": round(t_3d_fused, 1),
            "speedup_fused_vs_per_axis": round(speedup_3d, 2),
            "schemes": schemes_3d,
        },
        "3d_large": {
            "shape": list(SHAPE_3D_LARGE),
            "plan": fused3d.plan_3d(*SHAPE_3D_LARGE),
        },
        "codec": codec,
        "resilience": resilience,
        "ranges": ranges_sec,
        "serve": serve,
        "observability": observability,
    }
    rows = [
        ("kernels.platform", B.platform(), "probed once at import"),
        ("kernels.default_backend", B.default_backend(), "compiled by default"),
        ("kernels.bit_exact", int(bit_exact), "fused paths vs kernels/ref oracle"),
        (
            "kernels.1d.per_level_interpret_us",
            round(t_interp_1d, 1),
            f"{SHAPE_1D} x{LEVELS_1D} levels, seed hot path",
        ),
        (
            "kernels.1d.fused_compiled_us",
            round(t_fused_1d, 1),
            "fused multi-level; one compiled dispatch",
        ),
        (
            "kernels.1d.speedup",
            round(t_interp_1d / t_fused_1d, 2),
            "fused compiled vs per-level interpret",
        ),
        (
            "kernels.2d.per_level_interpret_us",
            round(t_interp_2d, 1),
            f"{SHAPE_2D}; 1D kernel + 4 transposes",
        ),
        (
            "kernels.2d.fused_compiled_us",
            round(t_fused_2d, 1),
            "fused row-column single pass",
        ),
        (
            "kernels.2d.speedup",
            round(t_interp_2d / t_fused_2d, 2),
            "fused compiled vs per-level interpret",
        ),
        (
            "kernels.2d_large.plan",
            plan_large,
            f"{SHAPE_2D_LARGE} execution path (tiled past the VMEM budget)",
        ),
        (
            "kernels.2d_large.fwd_us",
            round(t_large_fwd, 1),
            f"{SHAPE_2D_LARGE} single level, bit_exact={large_exact}",
        ),
        (
            "kernels.2d_pyramid.speedup",
            round(pyr_speedup, 2),
            f"fused {LEVELS_2D}-level pyramid vs per-level dispatch",
        ),
        (
            "kernels.2d_batched.images_per_s",
            round(imgs_per_s, 1),
            f"{SHAPE_2D_BATCH} batch->grid, speedup vs loop "
            f"{round(t_batch_loop / t_batch_fused, 2)}x",
        ),
    ]
    rows.extend(
        [
            (
                "kernels.3d.fused_us",
                round(t_3d_fused, 1),
                f"{SHAPE_3D} x{LEVELS_3D} levels fused N-D engine, "
                f"bit_exact={exact_3d}",
            ),
            (
                "kernels.3d.per_axis_us",
                round(t_3d_per_axis, 1),
                "per-axis 1D dispatches + moveaxis relayouts",
            ),
            (
                "kernels.3d.speedup",
                round(speedup_3d, 2),
                "fused 3D vs per-axis dispatch (drift-cancelled pairs)",
            ),
            (
                "kernels.3d_large.plan",
                fused3d.plan_3d(*SHAPE_3D_LARGE),
                f"{SHAPE_3D_LARGE} execution path (slab past the budget)",
            ),
        ]
    )
    for name, row in schemes_payload.items():
        rows.append(
            (
                f"kernels.scheme.{name}.fwd_1d_us",
                row["fwd_1d_us"],
                f"(8,4096)x3 levels; halo={row['halo']}, "
                f"{row['adders_per_pair']}add/{row['shifters_per_pair']}shift"
                f"/pair, bit_exact={row['bit_exact']}",
            )
        )
    for name, row in schemes_3d.items():
        rows.append(
            (
                f"kernels.scheme3d.{name}.fwd_us",
                row["fwd_us"],
                f"{SHAPE_3D_SCHEME} x2 levels, bit_exact={row['bit_exact']}",
            )
        )
    rows.extend(
        [
            (
                "kernels.codec.encode_mbps",
                codec["encode_mbps"],
                f"{SHAPE_CODEC} x{LEVELS_CODEC}-level pyramid -> WZRC "
                "container (raw int32 MB/s)",
            ),
            (
                "kernels.codec.decode_mbps",
                codec["decode_mbps"],
                "WZRC container -> pyramid",
            ),
            (
                "kernels.codec.smooth.ratio_vs_zlib",
                codec["smooth"]["ratio_vs_zlib"],
                f"wz-rice {codec['smooth']['wz_rice_bytes']}B vs plain zlib "
                f"{codec['smooth']['zlib_bytes']}B on a smooth "
                "checkpoint-like tensor",
            ),
            (
                "kernels.codec.noisy.ratio_vs_zlib",
                codec["noisy"]["ratio_vs_zlib"],
                f"wz-rice {codec['noisy']['wz_rice_bytes']}B vs plain zlib "
                f"{codec['noisy']['zlib_bytes']}B on gaussian noise",
            ),
        ]
    )
    for name, ok in codec["lossless"].items():
        rows.append(
            (
                f"kernels.codec.lossless.{name}",
                int(ok),
                "container roundtrip bit-exact across 1D/2D/3D pyramids",
            )
        )
    rows.extend(
        [
            (
                "kernels.resilience.parity_overhead_ratio",
                resilience["parity_overhead_ratio"],
                f"XOR parity group adds "
                f"{resilience['parity_overhead_bytes']}B to a "
                f"{resilience['container_bytes']}B WZRC v2 container",
            ),
            (
                "kernels.resilience.single_band_recovery",
                int(resilience["single_band_recovery"]),
                "byte flipped mid-band; parity heals the decode bit-exactly",
            ),
        ]
    )
    for cls, outcome in resilience["recovery"].items():
        rows.append(
            (
                f"kernels.resilience.{cls}",
                outcome,
                "measured fault outcome (gate.py pins the expectation)",
            )
        )
    for name, cert in ranges_sec["certificates"].items():
        rows.append(
            (
                f"kernels.ranges.{name}.safe_abs_1d_l1",
                cert["safe_abs_1d_l1"],
                f"largest |input| certified int32-safe (1 level, 1D); "
                f"growth {cert['growth_bits_1d_l1']} bits/level",
            )
        )
    for eng, out in ranges_sec["wraparound"].items():
        rows.append(
            (
                f"kernels.ranges.checked.{eng}",
                out,
                "checked mode on an int32-wrapping input (gate pins "
                "typed-error)",
            )
        )
    rows.extend(
        [
            (
                "kernels.ranges.roundtrip_exact",
                int(ranges_sec["roundtrip_exact"]),
                "certificate-respecting input, checked=True, bit-exact",
            ),
            (
                "kernels.ranges.overhead_off_x",
                ranges_sec["overhead_off_x"],
                "checked=False vs default (drift-cancelled; ~1.0 = free)",
            ),
            (
                "kernels.ranges.overhead_on_x",
                ranges_sec["overhead_on_x"],
                "checked=True vs default (host interval walk cost)",
            ),
        ]
    )
    rows.extend(
        [
            (
                "kernels.serve.requests_per_s",
                serve["requests_per_s"],
                f"{serve['requests']} mixed-bucket requests, "
                f"{serve['batch_slots']} slots, buckets {serve['buckets']}",
            ),
            (
                "kernels.serve.p99_ms",
                serve["p99_ms"],
                "submit-to-completion tail latency (warm cache)",
            ),
            (
                "kernels.serve.cache_hit_rate",
                serve["cache_hit_rate"],
                f"executable cache after warmup ({serve['compiles']} "
                "compiles total; gate pins 1.0)",
            ),
            (
                "kernels.serve.batch_encode_speedup",
                serve["batch_encode_speedup"],
                f"one WZRC container per micro-batch "
                f"({serve['batch_encode_ms']}ms) vs per-request loop "
                f"({serve['per_request_encode_ms']}ms); gate pins >= 1.5",
            ),
            (
                "kernels.serve.thumbnail_bytes_fraction",
                serve["thumbnail_bytes_fraction"],
                "progressive LL-tier bytes read / stored container bytes",
            ),
        ]
    )
    rows.extend(
        [
            (
                "kernels.obs.overhead_x",
                observability["overhead_x"],
                "serve throughput, instrumented vs obs.disabled() "
                "(drift-cancelled pairs; gate pins <= 1.10)",
            ),
            (
                "kernels.obs.event_total",
                observability["event_total"],
                "structured events from one seeded chaos run: "
                + ", ".join(
                    f"{k}={v}"
                    for k, v in sorted(observability["events"].items())
                ),
            ),
            (
                "kernels.obs.subsystems",
                "+".join(observability["metric_subsystems"]),
                "subsystems with live metric series after the chaos run "
                "(gate pins all five)",
            ),
        ]
    )
    return rows, payload


def run() -> list:
    rows, _ = run_json()
    return rows
