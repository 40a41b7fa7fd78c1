"""Chaos suite: every injected fault class resolves cleanly.

The invariant (DESIGN.md §12): an injected fault must end in exactly one
of three outcomes — **bit-exact recovery**, **typed-warning
degradation**, or a **typed error** — never silent corruption and never
a bare ``struct.error`` / ``IndexError`` leaking from a parser.

Runs as its own CI lane (``pytest -m chaos``) with a fixed injection
seed; override locally with ``REPRO_CHAOS_SEED=<n>`` to replay a
different deterministic damage pattern.  Every test disarms the global
fault registry around itself, so chaos state never leaks between tests.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import kernels as K
from repro import obs
from repro.codec import (
    CodecError,
    CorruptBandError,
    CorruptHeaderError,
    TruncatedStreamError,
    decode_pyramid,
    decode_pyramid_partial,
    encode_pyramid,
    peek,
)
from repro.codec import stream as wzrs
from repro.ckpt.checkpoint import CheckpointManager
from repro.ckpt.ft import StragglerWatchdog
from repro.resilience import (
    CheckpointIntegrityError,
    DeadlineExceededError,
    DegradedRestoreWarning,
    InjectedFault,
    LoadShedError,
    RetryExhaustedError,
    RetryWarning,
    corrupt,
    flip_byte,
    inject,
    truncate,
)
from repro.serve.serve_step import TransformRequest, WaveletServeEngine

pytestmark = pytest.mark.chaos

CHAOS_SEED = int(os.environ.get("REPRO_CHAOS_SEED", "1010"))


@pytest.fixture(autouse=True)
def _clean_faults():
    inject.reset()
    yield
    inject.reset()


def _pyramids_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _pyramid(seed=0, shape=(2, 24, 40), levels=2):
    rng = np.random.default_rng(seed)
    x = rng.integers(-200, 200, shape, dtype=np.int32)
    return K.dwt_fwd_2d_multi(x, levels=levels)


# ---------------------------------------------------------------------------
# WZRC v2: per-band CRCs, XOR parity self-healing, partial decode.
# ---------------------------------------------------------------------------


def test_parity_heals_every_band():
    """Damage each band in turn; parity reconstructs all of them."""
    pyr = _pyramid()
    blob = encode_pyramid(pyr, parity=True)
    h = peek(blob)
    # band blobs start right after the header; walk the recorded lengths
    body_off = len(blob) - sum(h["band_bytes"]) - h["parity_bytes"]
    off = body_off
    for i, blen in enumerate(h["band_bytes"]):
        bad = flip_byte(blob, off + blen // 2)
        dec = decode_pyramid(bad)
        assert dec.band_status[i] == "reconstructed"
        assert all(
            s == "ok" for j, s in enumerate(dec.band_status) if j != i
        )
        assert _pyramids_equal(dec.pyramid, pyr)
        off += blen


def test_parity_off_band_damage_raises_typed():
    pyr = _pyramid()
    blob = encode_pyramid(pyr, parity=False)
    bad = flip_byte(blob, len(blob) // 2)
    with pytest.raises(CorruptBandError, match="corrupt"):
        decode_pyramid(bad)


def test_partial_decode_quarantines_only_damaged_band():
    pyr = _pyramid()
    blob = encode_pyramid(pyr, parity=False)
    h = peek(blob)
    body_off = len(blob) - sum(h["band_bytes"])
    # damage band 0 (the approx band)
    bad = flip_byte(blob, body_off + h["band_bytes"][0] // 2)
    part = decode_pyramid_partial(bad)
    assert part.band_status[0] == "corrupt"
    assert all(s == "ok" for s in part.band_status[1:])
    assert not part.complete
    # every surviving band is bit-exact; the damaged one is zero-filled
    want = jax.tree_util.tree_leaves(pyr)
    got = jax.tree_util.tree_leaves(part.pyramid)
    assert np.count_nonzero(np.asarray(got[0])) == 0
    for g, w in zip(got[1:], want[1:]):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_two_damaged_bands_exceed_parity():
    """XOR parity is single-erasure; double damage must raise, not heal
    wrong."""
    pyr = _pyramid()
    blob = encode_pyramid(pyr, parity=True)
    h = peek(blob)
    body_off = len(blob) - sum(h["band_bytes"]) - h["parity_bytes"]
    bad = flip_byte(blob, body_off + h["band_bytes"][0] // 2)
    bad = flip_byte(bad, body_off + h["band_bytes"][0] + h["band_bytes"][1] // 2)
    with pytest.raises(CorruptBandError):
        decode_pyramid(bad)
    part = decode_pyramid_partial(bad)
    assert part.band_status[0] == "corrupt"
    assert part.band_status[1] == "corrupt"


def test_header_damage_always_typed():
    """Any header byte flip raises a typed CodecError — geometry is never
    partially trusted."""
    pyr = _pyramid()
    blob = encode_pyramid(pyr, parity=True)
    h = peek(blob)
    body_off = len(blob) - sum(h["band_bytes"]) - h["parity_bytes"]
    for i in range(body_off):
        with pytest.raises(CodecError):
            decode_pyramid(flip_byte(blob, i))


def test_seeded_flip_sweep_never_silently_corrupts():
    """The chaos invariant, swept: a seeded random bit flip anywhere in
    the container either heals bit-exactly or raises a typed error."""
    pyr = _pyramid(seed=CHAOS_SEED)
    blob = encode_pyramid(pyr, parity=True)
    healed = raised = 0
    for trial in range(64):
        bad = corrupt(blob, seed=CHAOS_SEED + trial, n_bits=1)
        try:
            dec = decode_pyramid(bad)
        except CodecError:
            raised += 1
            continue
        # decoded: the result must be bit-exact, damage healed or benign
        assert _pyramids_equal(dec.pyramid, pyr), (
            f"silent corruption at chaos seed {CHAOS_SEED + trial}"
        )
        healed += 1
    assert healed + raised == 64
    assert healed > 0  # the sweep must actually exercise the heal path


def test_truncation_typed_error():
    pyr = _pyramid()
    for parity in (False, True):
        blob = encode_pyramid(pyr, parity=parity)
        for keep in (len(blob) - 3, len(blob) // 2, 9, 3):
            with pytest.raises(CodecError):
                decode_pyramid(truncate(blob, keep))


def test_v1_interop_both_ways():
    """v1 blobs decode under the v2 reader; v1 writer output is
    byte-stable and the v2 default never emits it."""
    pyr = _pyramid()
    v1 = encode_pyramid(pyr, version=1)
    assert v1[4] == 1
    dec = decode_pyramid(v1)
    assert _pyramids_equal(dec.pyramid, pyr)
    assert all(s == "ok" for s in dec.band_status)
    v2 = encode_pyramid(pyr)
    assert v2[4] == 2
    assert peek(v2)["version"] == 2
    # v1 whole-blob CRC still enforced
    with pytest.raises(CodecError, match="checksum|corrupt|truncated"):
        decode_pyramid(flip_byte(v1, len(v1) // 2))


def test_parity_overhead_is_one_band():
    pyr = _pyramid()
    plain = encode_pyramid(pyr, parity=False)
    withp = encode_pyramid(pyr, parity=True)
    h = peek(withp)
    assert h["parity_bytes"] == max(h["band_bytes"])
    # parity adds one max-band blob plus the 4-byte parity CRC field;
    # both layouts carry the same fixed header otherwise
    assert len(withp) - len(plain) == h["parity_bytes"]


def test_corrupt_is_deterministic():
    data = bytes(range(256)) * 8
    a = corrupt(data, seed=CHAOS_SEED, n_bits=5)
    b = corrupt(data, seed=CHAOS_SEED, n_bits=5)
    c = corrupt(data, seed=CHAOS_SEED + 1, n_bits=5)
    assert a == b
    assert a != c
    assert len(a) == len(data)


# ---------------------------------------------------------------------------
# WZRS stream: mid-frame truncation, garbage headers (satellite).
# ---------------------------------------------------------------------------


def test_stream_mid_frame_truncation_prior_frames_survive():
    rng = np.random.default_rng(CHAOS_SEED)
    vol = rng.integers(-100, 100, (6, 16, 16), dtype=np.int32)
    data = b"".join(wzrs.encode_volume(vol, slab=2, levels=1))
    # count full frames, then cut inside the LAST frame's body
    frames = list(wzrs.iter_frames(data))
    assert len(frames) == 3
    last_len = len(frames[-1])
    cut = data[: len(data) - 4 - last_len // 2]  # drop trailer + half a frame
    out = []
    with pytest.raises(TruncatedStreamError, match="truncated"):
        for chunk in wzrs.decode_stream(cut):
            out.append(chunk)
    # every frame before the cut decoded bit-exactly
    assert len(out) == 2
    assert np.array_equal(np.concatenate(out), vol[:4])


def test_stream_garbage_header_typed():
    with pytest.raises(CorruptHeaderError, match="magic"):
        list(wzrs.iter_frames(b"JUNK" + b"\x00" * 64))
    with pytest.raises(CodecError, match="version"):
        list(wzrs.iter_frames(b"WZRS\x63\x00\x00\x00" + b"\x00" * 8))
    # truncated mid-header
    with pytest.raises(TruncatedStreamError):
        list(wzrs.iter_frames(b"WZ"))


def test_stream_frame_with_corrupt_container_typed():
    rng = np.random.default_rng(CHAOS_SEED)
    vol = rng.integers(-50, 50, (4, 16, 16), dtype=np.int32)
    data = bytearray(b"".join(wzrs.encode_volume(vol, slab=2, levels=1)))
    data[len(data) // 2] ^= 0xFF  # inside some frame's container body
    with pytest.raises(CodecError):
        list(wzrs.decode_stream(bytes(data)))


# ---------------------------------------------------------------------------
# Checkpoint: crash at every save stage, async surfacing, self-healing.
# ---------------------------------------------------------------------------


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.standard_normal((24, 40)).astype(np.float32),
        "b": rng.standard_normal((40,)).astype(np.float32),
    }


@pytest.mark.parametrize(
    "site",
    [
        "ckpt.save.before_write",
        "ckpt.save.mid_write",
        "ckpt.save.before_commit",
    ],
)
def test_save_crash_leaves_previous_intact(tmp_path, site):
    """A crash before the commit rename never produces a new step and
    never damages the previous one."""
    mgr = CheckpointManager(tmp_path, codec="z")
    tree = _tree()
    mgr.save(1, tree)
    with inject.armed(site):
        with pytest.raises(InjectedFault):
            mgr.save(2, _tree(seed=2))
    assert mgr.latest_step() == 1
    step, restored = mgr.restore(template=tree)
    assert step == 1
    assert np.array_equal(restored["w"], tree["w"])
    # no half-written droppings for a reader (or GC) to trip over
    assert not list(tmp_path.glob(".tmp_step_*"))


def test_save_crash_before_latest_falls_back_to_scan(tmp_path):
    """A crash between the step commit and the LATEST update: the step IS
    complete on disk, and latest_step finds it by scanning."""
    mgr = CheckpointManager(tmp_path, codec="z")
    tree = _tree()
    mgr.save(1, tree)
    with inject.armed("ckpt.save.before_latest"):
        with pytest.raises(InjectedFault):
            mgr.save(2, _tree(seed=2))
    assert (tmp_path / "LATEST").read_text().strip() == "step_0000000001"
    assert mgr.latest_step() == 2  # fallback scan sees the committed dir
    step, _ = mgr.restore(template=tree)
    assert step == 2


def test_async_save_failure_surfaces_in_wait(tmp_path):
    mgr = CheckpointManager(tmp_path, codec="z")
    with inject.armed("ckpt.save.before_commit"):
        mgr.save(1, _tree(), blocking=False)
        with pytest.raises(InjectedFault):
            mgr.wait()
    # the failure is consumed: the next save/wait cycle is clean
    mgr.save(2, _tree(seed=2), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 2


def test_wzrice_leaf_self_heals_with_warning(tmp_path):
    """Flip a byte inside a wz-rice leaf payload: sha256 fails, the
    container's parity heals, restore warns and returns the exact
    tensor."""
    mgr = CheckpointManager(tmp_path, codec="wz-rice", parity=True)
    tree = _tree()
    mgr.save(1, tree)
    _, clean = mgr.restore(template=tree)
    step_dir = tmp_path / "step_0000000001"
    leaf = step_dir / "w.bin"
    data = leaf.read_bytes()
    body = len(data) // 2  # deep in the band payload, past the header
    leaf.write_bytes(flip_byte(data, body))
    with pytest.warns(DegradedRestoreWarning, match="self-heal|per-band"):
        step, healed = mgr.restore(template=tree)
    assert step == 1
    # bit-identical to the undamaged restore (the codec is lossy only at
    # quantization, which already happened at save)
    assert np.array_equal(healed["w"], clean["w"])


def test_wzrice_leaf_unhealable_raises_checksum_ioerror(tmp_path):
    mgr = CheckpointManager(tmp_path, codec="wz-rice", parity=False)
    tree = _tree()
    mgr.save(1, tree)
    step_dir = tmp_path / "step_0000000001"
    leaf = step_dir / "w.bin"
    data = leaf.read_bytes()
    leaf.write_bytes(flip_byte(data, 8))  # header damage: unhealable
    with pytest.raises(IOError, match="checksum"):
        mgr.restore(template=tree)
    with pytest.raises(CheckpointIntegrityError):
        mgr.restore(template=tree)


# ---------------------------------------------------------------------------
# Kernel dispatch: a pallas failure raises — no fallback hides the device.
# ---------------------------------------------------------------------------


def test_pallas_failure_degrades_bit_exact():
    """An armed kernel fault surfaces as the typed error on every call —
    never a silent recompute on the XLA reference — and records a
    kernels FaultEvent naming the site; the next clean call is exact."""
    rng = np.random.default_rng(CHAOS_SEED)
    x = rng.integers(-300, 300, (3, 24, 40), dtype=np.int32)
    want = K.dwt_fwd_2d_multi(x, levels=2, backend="xla")
    obs.events.reset()
    with inject.armed("kernels.pallas", times=None):
        for _ in range(2):
            with pytest.raises(InjectedFault):
                K.dwt_fwd_2d_multi(x, levels=2, backend="interpret")
    faults = obs.events.query(obs.FaultEvent, subsystem="kernels")
    assert [e.site for e in faults] == ["dwt_fwd_2d_multi"] * 2
    got = K.dwt_fwd_2d_multi(x, levels=2, backend="interpret")
    assert _pyramids_equal(got, want)


def test_pallas_failure_1d_and_nd_guarded():
    rng = np.random.default_rng(CHAOS_SEED)
    x1 = rng.integers(-100, 100, (64,), dtype=np.int32)
    x3 = rng.integers(-100, 100, (8, 8, 8), dtype=np.int32)
    with inject.armed("kernels.pallas", times=None):
        with pytest.raises(InjectedFault):
            K.dwt_fwd(x1, levels=2, backend="interpret")
        with pytest.raises(InjectedFault):
            K.dwt_fwd_nd(x3, levels=1, backend="interpret", ndim=3)
        # the XLA path never enters the kernel guard's fault site
        p1 = K.dwt_fwd(x1, levels=2, backend="xla")
    assert _pyramids_equal(
        p1, K.dwt_fwd(x1, levels=2, backend="interpret")
    )


# ---------------------------------------------------------------------------
# Sharded collectives: stuck-neighbor watchdog.
# ---------------------------------------------------------------------------


def test_collective_watchdog_times_out():
    from repro.resilience.errors import CollectiveTimeoutError

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    rng = np.random.default_rng(CHAOS_SEED)
    x = rng.integers(-100, 100, (16, 16), dtype=np.int32)
    # a healthy mesh completes under the watchdog
    pyr = K.dwt_fwd_2d_sharded(x, mesh, levels=1, timeout_s=30.0)
    assert _pyramids_equal(pyr, K.dwt_fwd_2d_multi(x, levels=1))
    # a stuck neighbor (simulated: delay inside the timed region) times out
    with inject.armed("sharded.collective", action="delay", delay_s=1.0):
        with pytest.raises(CollectiveTimeoutError, match="stuck"):
            K.dwt_fwd_2d_sharded(x, mesh, levels=1, timeout_s=0.05)
    # after the fault clears, the engine still serves
    pyr2 = K.dwt_fwd_2d_sharded(x, mesh, levels=1, timeout_s=30.0)
    assert _pyramids_equal(pyr2, pyr)


# ---------------------------------------------------------------------------
# Serve: deadlines, bounded retry, load shedding.
# ---------------------------------------------------------------------------


def _image(seed=0, h=16, w=16):
    return np.random.default_rng(seed).integers(
        -100, 100, (h, w), dtype=np.int32
    )


def test_serve_transient_failure_retries_then_succeeds():
    eng = WaveletServeEngine(
        height=16, width=16, levels=1, batch_slots=2, retry_backoff_s=0.001
    )
    eng.submit(TransformRequest(uid=1, image=_image(1)))
    with inject.armed("serve.transform", times=1):  # first attempt only
        with pytest.warns(RetryWarning, match="retrying"):
            done = eng.step()
    assert len(done) == 1 and done[0].done and done[0].error is None
    want = K.dwt_fwd_2d_multi(_image(1)[None], levels=1)
    assert _pyramids_equal(
        done[0].pyramid, jax.tree_util.tree_map(lambda b: b[0], want)
    )


def test_serve_retry_exhaustion_requeues_and_raises():
    eng = WaveletServeEngine(
        height=16, width=16, levels=1, max_retries=1, retry_backoff_s=0.001
    )
    eng.submit(TransformRequest(uid=1, image=_image(1)))
    with inject.armed("serve.transform", times=None):  # permanent fault
        with pytest.raises(RetryExhaustedError, match="2 attempts"):
            eng.step()
    # no request lost: once the fault clears, the queue drains normally
    done = eng.step()
    assert len(done) == 1 and done[0].done


def test_serve_deadline_miss_is_per_request():
    eng = WaveletServeEngine(height=16, width=16, levels=1, deadline_s=0.01)
    late = TransformRequest(uid=1, image=_image(1))
    eng.submit(late)
    import time as _time

    _time.sleep(0.05)  # deadline passes while the request queues
    fresh = TransformRequest(uid=2, image=_image(2))
    eng.submit(fresh)
    done = eng.step()
    by_uid = {r.uid: r for r in done}
    assert isinstance(by_uid[1].error, DeadlineExceededError)
    assert not by_uid[1].done and by_uid[1].pyramid is None
    assert by_uid[2].done and by_uid[2].error is None  # unpoisoned


def test_serve_load_shedding_admission():
    eng = WaveletServeEngine(height=16, width=16, levels=1, max_queue=2)
    eng.submit(TransformRequest(uid=1, image=_image(1)))
    eng.submit(TransformRequest(uid=2, image=_image(2)))
    with pytest.raises(LoadShedError, match="shed"):
        eng.submit(TransformRequest(uid=3, image=_image(3)))
    done = eng.step()  # draining frees budget
    assert len(done) == 2
    eng.submit(TransformRequest(uid=3, image=_image(3)))  # admitted now


def test_serve_encode_failure_degrades_per_request():
    """The encode degradation ladder: batch container -> per-request loop
    -> per-request quarantine.  A failed batch-level encode falls back to
    the PR 6 per-request loop, and a per-request failure inside THAT
    quarantines one request without poisoning its batchmates."""
    eng = WaveletServeEngine(
        height=16, width=16, levels=1, encode_response=True, batch_slots=2
    )
    eng.submit(TransformRequest(uid=1, image=_image(1)))
    eng.submit(TransformRequest(uid=2, image=_image(2)))
    with inject.armed("serve.encode_batch", times=1):  # force the fallback
        with inject.armed("serve.encode", at_call=1, times=1):  # then uid 1
            done = eng.step()
    by_uid = {r.uid: r for r in done}
    assert by_uid[1].done and by_uid[1].encoded is None
    assert isinstance(by_uid[1].error, InjectedFault)
    assert by_uid[1].pyramid is not None  # the transform result still serves
    assert by_uid[2].encoded is not None and by_uid[2].error is None
    assert by_uid[2].batch_index is None  # per-request container
    dec = decode_pyramid(by_uid[2].encoded)
    assert _pyramids_equal(dec.pyramid, by_uid[2].pyramid)


# ---------------------------------------------------------------------------
# Watchdog boundedness (satellite).
# ---------------------------------------------------------------------------


def test_straggler_watchdog_is_bounded():
    wd = StragglerWatchdog(window=8, flagged_cap=4)
    for step in range(1000):
        # occasional 500x spike over an otherwise steady cadence
        wd.observe(step, 5.0 if step % 10 == 0 else 0.01)
    assert len(wd.history) <= 8
    assert len(wd.flagged) == 4  # ~100 flags raised, ring keeps the last 4
    assert wd.flagged[-1]["step"] == 990  # newest kept, oldest evicted
    assert wd.flagged[0]["step"] == 960
