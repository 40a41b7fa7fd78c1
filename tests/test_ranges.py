"""Certified integer range safety (core.ranges) and checked mode.

Four layers under test:

1. the DERIVATION — exact interval tracing of the lifting cascade, with
   differential sweeps asserting every band value an engine actually
   produces lies inside the traced interval, and that certificates sit
   exactly on the safe/unsafe boundary (nothing is hardcoded per
   scheme);
2. the CHECKED EXECUTION MODE — ``checked=True`` / ``REPRO_DWT_CHECKED``
   on every engine (oracle 1D/2D/N-D, fused 1D/2D/3D, tiled, sharded)
   raises :class:`IntegerOverflowError` for wrap-capable inputs and is
   bit-exact and silent on certified inputs;
3. the ADVERSARIAL EXTREMES — int32 ``iinfo.min``/``iinfo.max`` samples
   through every engine must either round-trip bit-exactly (modular
   lifting is still invertible) or raise the typed error, never return
   a silently-mismatched reconstruction;
4. the BOUNDARIES — codec encode, checkpoint wavelet codecs, gradient
   quantization and serve admission all consult the certificates.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro import kernels as K
from repro.core import lifting as L
from repro.core import ranges
from repro.resilience.errors import IntegerOverflowError, ResilienceError

SCHEMES = ("cdf53", "haar", "cdf22", "97m")
MODES = ("paper", "jpeg2000")
I32 = np.iinfo(np.int32)


@pytest.fixture(autouse=True)
def _neutral_checked_env(monkeypatch):
    """Pin the env toggle off so every test states its own checked mode.

    The CI chaos lane exports ``REPRO_DWT_CHECKED=1`` over this file;
    the default-off assertions (wraparound tolerated, boundaries silent)
    must stay deterministic under it.  Tests that exercise the env
    toggle re-set it explicitly via monkeypatch.
    """
    monkeypatch.delenv("REPRO_DWT_CHECKED", raising=False)


def _rand(shape, lo, hi, seed=0, dtype=np.int32):
    return jnp.asarray(
        np.random.default_rng(seed).integers(lo, hi + 1, shape), dtype
    )


# ---------------------------------------------------------------------------
# Derivation: traced intervals bound reality; certificates are exact.
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(
    scheme=st.sampled_from(SCHEMES),
    mode=st.sampled_from(MODES),
    levels=st.integers(1, 3),
    mag_bits=st.integers(0, 20),
    seed=st.integers(0, 2**31 - 1),
)
def test_trace_forward_bounds_actual_bands_1d(scheme, mode, levels, mag_bits, seed):
    """Differential sweep: every band value of real data lies inside the
    interval trace of that data's hull — the soundness contract."""
    mag = 1 << mag_bits
    x = _rand((2, 32), -mag, mag, seed)
    ft = ranges.trace_forward(
        scheme, levels, ranges.Interval(-mag, mag), mode=mode, ndim=1
    )
    pyr = L.dwt_fwd(x, levels=levels, mode=mode, scheme=scheme)
    a = np.asarray(pyr.approx)
    assert ft.approx.lo <= a.min() and a.max() <= ft.approx.hi
    # lifting.WaveletPyramid stores details coarsest-first; trace level
    # order is outermost-first, so index from the other end
    for lvl, band in enumerate(reversed(pyr.details)):
        b = np.asarray(band)
        iv = ft.details[lvl][0]
        assert iv.lo <= b.min() and b.max() <= iv.hi


@settings(max_examples=10, deadline=None)
@given(
    scheme=st.sampled_from(SCHEMES),
    mode=st.sampled_from(MODES),
    seed=st.integers(0, 2**31 - 1),
)
def test_trace_forward_bounds_actual_bands_2d(scheme, mode, seed):
    mag = 4096
    x = _rand((2, 16, 16), -mag, mag, seed)
    ft = ranges.trace_forward(
        scheme, 2, ranges.Interval(-mag, mag), mode=mode, ndim=2
    )
    pyr = L.dwt_fwd_2d_multi(x, levels=2, mode=mode, scheme=scheme)
    hull = ft.band_hull()
    for band in jax.tree_util.tree_leaves(pyr):
        b = np.asarray(band)
        assert hull.lo <= b.min() and b.max() <= hull.hi


@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("ndim", [1, 2, 3])
def test_certificate_is_exact_boundary(scheme, ndim):
    """cert.hi is the LARGEST safe magnitude: the trace at the bound fits
    the compute dtype and the trace one past it does not (unless the
    whole dtype range is safe) — proof the value is derived, not guessed."""
    cert = ranges.range_certificate(scheme, 2, np.int32, ndim=ndim)
    ext = ranges.cascade_extremes(
        scheme, 2, ranges.Interval(cert.lo, cert.hi), ndim=ndim
    )
    assert I32.min <= ext.lo and ext.hi <= I32.max
    if cert.hi < I32.max:
        ext2 = ranges.cascade_extremes(
            scheme, 2, ranges.Interval(-(cert.hi + 1), cert.hi + 1), ndim=ndim
        )
        assert ext2.lo < I32.min or ext2.hi > I32.max


def test_certificates_shrink_with_levels_and_ndim():
    for scheme in SCHEMES:
        c1 = ranges.range_certificate(scheme, 1, np.int32)
        c2 = ranges.range_certificate(scheme, 2, np.int32)
        c3 = ranges.range_certificate(scheme, 3, np.int32)
        assert c1.hi >= c2.hi >= c3.hi > 0
        d1 = ranges.range_certificate(scheme, 1, np.int32, ndim=2)
        d2 = ranges.range_certificate(scheme, 1, np.int32, ndim=3)
        assert c1.hi >= d1.hi >= d2.hi > 0


def test_certified_levels_consistent_with_certificates():
    for scheme in SCHEMES:
        cert = ranges.range_certificate(scheme, 2, np.int32, ndim=2)
        n = ranges.certified_levels(
            scheme, np.int32, (cert.lo, cert.hi), ndim=2
        )
        assert n >= 2
        # one past the certified bound must certify strictly fewer levels
        if cert.hi < I32.max:
            m = ranges.certified_levels(
                scheme, np.int32, (-(cert.hi + 1), cert.hi + 1), ndim=2
            )
            assert m < 2
    # out-of-dtype input certifies nothing
    assert ranges.certified_levels("cdf53", np.int32, (0, 2**40)) == 0


def test_narrow_dtypes_always_certify_deep_pyramids():
    """int16-range data in int32 compute has >= 5 cdf53 levels of room —
    the paper's 8-bit-sample regime never needs a headroom thought."""
    for scheme in ("cdf53", "haar"):
        assert ranges.certified_levels(scheme, np.int16, (-32768, 32767)) >= 5
    cert = ranges.range_certificate("cdf53", 3, np.int16)
    assert cert.hi == 32767  # whole dtype certified: compute is int32


def test_trace_inverse_and_band_safe_input():
    ft = ranges.trace_forward("cdf53", 2, ranges.Interval(-1000, 1000), ndim=2)
    it = ranges.trace_inverse(
        "cdf53", 2, ft.approx, ft.details, ndim=2
    )
    # inverse of the traced bands contains the original input interval
    assert it.approx.lo <= -1000 and 1000 <= it.approx.hi
    # band_safe_input: bands provably fit int16 at the derived magnitude
    m = ranges.band_safe_input("cdf53", 2, 32767, mode="paper", ndim=1)
    bh = ranges.trace_forward(
        "cdf53", 2, ranges.Interval(-m, m), mode="paper"
    ).band_hull()
    assert -32767 <= bh.lo and bh.hi <= 32767
    bh2 = ranges.trace_forward(
        "cdf53", 2, ranges.Interval(-(m + 1), m + 1), mode="paper"
    ).band_hull()
    assert bh2.lo < -32767 or bh2.hi > 32767


# ---------------------------------------------------------------------------
# Checked execution mode, every engine.
# ---------------------------------------------------------------------------


def _oracle_1d(x, checked=None):
    pyr = L.dwt_fwd(x, levels=2, scheme="cdf53", checked=checked)
    return L.dwt_inv(pyr, scheme="cdf53", checked=checked)


def _oracle_2d(x, checked=None):
    pyr = L.dwt_fwd_2d_multi(x, levels=2, scheme="cdf53", checked=checked)
    return L.dwt_inv_2d_multi(pyr, scheme="cdf53", checked=checked)


def _oracle_nd(x, checked=None):
    pyr = L.dwt_fwd_nd(x, levels=2, scheme="cdf53", ndim=3, checked=checked)
    return L.dwt_inv_nd(pyr, scheme="cdf53", checked=checked)


def _fused_1d(x, checked=None):
    pyr = K.dwt_fwd(x, levels=2, scheme="cdf53", checked=checked)
    return K.dwt_inv(pyr, scheme="cdf53", checked=checked)


def _fused_2d(x, checked=None):
    pyr = K.dwt_fwd_2d_multi(x, levels=2, scheme="cdf53", checked=checked)
    return K.dwt_inv_2d_multi(pyr, scheme="cdf53", checked=checked)


def _fused_3d(x, checked=None):
    pyr = K.dwt_fwd_nd(x, levels=2, scheme="cdf53", ndim=3, checked=checked)
    return K.dwt_inv_nd(pyr, scheme="cdf53", checked=checked)


ENGINES_2D_SHAPE = (2, 16, 16)
ENGINES = [
    ("oracle-1d", _oracle_1d, (2, 32)),
    ("oracle-2d", _oracle_2d, ENGINES_2D_SHAPE),
    ("oracle-nd", _oracle_nd, (8, 8, 8)),
    ("fused-1d", _fused_1d, (2, 32)),
    ("fused-2d", _fused_2d, ENGINES_2D_SHAPE),
    ("fused-3d", _fused_3d, (8, 8, 8)),
]


@pytest.mark.parametrize("name,roundtrip,shape", ENGINES)
def test_checked_mode_rejects_wraparound(name, roundtrip, shape):
    x = jnp.full(shape, I32.max, jnp.int32)
    with pytest.raises(IntegerOverflowError):
        roundtrip(x, checked=True)


@pytest.mark.parametrize("name,roundtrip,shape", ENGINES)
def test_checked_mode_certified_inputs_roundtrip(name, roundtrip, shape):
    cert = ranges.range_certificate(
        "cdf53", 2, np.int32, ndim=len(shape) - 1 if len(shape) > 2 else 1
    )
    # samples AT the certified bound: the hardest legal input
    rng = np.random.default_rng(7)
    x = jnp.asarray(
        rng.choice(np.array([cert.lo, 0, cert.hi], np.int64), shape), jnp.int32
    )
    xr = roundtrip(x, checked=True)
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


def test_checked_mode_tiled_engine(monkeypatch):
    monkeypatch.setenv("REPRO_DWT_TILE", "8")
    x = jnp.full((1, 16, 16), I32.max, jnp.int32)
    with pytest.raises(IntegerOverflowError):
        K.dwt_fwd_2d_multi(x, levels=2, checked=True)
    ok = _rand((1, 16, 16), -4096, 4096, 3)
    pyr = K.dwt_fwd_2d_multi(ok, levels=2, checked=True)
    xr = K.dwt_inv_2d_multi(pyr, checked=True)
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(ok))


def test_checked_mode_sharded_engine():
    from jax.sharding import Mesh

    from repro.kernels import sharded

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = jnp.full((16, 16), I32.max, jnp.int32)
    with pytest.raises(IntegerOverflowError):
        sharded.dwt_fwd_2d_sharded(x, mesh, levels=2, checked=True)
    ok = _rand((16, 16), -4096, 4096, 4)
    pyr = sharded.dwt_fwd_2d_sharded(ok, mesh, levels=2, checked=True)
    xr = sharded.dwt_inv_2d_sharded(pyr, mesh, checked=True)
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(ok))


def test_checked_mode_measured_not_static():
    """The per-level measured walk admits real data a static full-cascade
    trace would reject: 97m 2D x3 levels at +-4096 is far outside the
    worst-case certificate yet provably safe for actual samples."""
    cert = ranges.range_certificate("97m", 3, np.int32, ndim=2)
    assert cert.hi < 4096  # static worst case genuinely excludes this
    x = _rand((1, 32, 32), -4096, 4096, 5)
    pyr = L.dwt_fwd_2d_multi(x, levels=3, scheme="97m", checked=True)
    xr = L.dwt_inv_2d_multi(pyr, scheme="97m", checked=True)
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


def test_checked_inverse_rejects_hostile_bands():
    """Bands that are NOT the forward image of any in-range input (e.g.
    a foreign bitstream) make the inverse wrap; the checked inverse
    post-verifies the reconstruction and raises instead of returning it."""
    hp = L.WaveletPyramid(
        approx=jnp.full((1, 8), I32.max, jnp.int32),
        details=(
            jnp.full((1, 8), I32.max, jnp.int32),
            jnp.full((1, 16), I32.max, jnp.int32),
        ),
    )
    with pytest.raises(IntegerOverflowError):
        L.dwt_inv(hp, checked=True)
    with pytest.raises(IntegerOverflowError):
        K.dwt_inv(hp, checked=True)


def test_env_toggle_and_kwarg_precedence(monkeypatch):
    x = jnp.full((1, 32), I32.max, jnp.int32)
    monkeypatch.setenv("REPRO_DWT_CHECKED", "1")
    with pytest.raises(IntegerOverflowError):
        L.dwt_fwd(x, levels=1)
    # explicit kwarg wins over the env toggle
    pyr = L.dwt_fwd(x, levels=1, checked=False)
    assert pyr.approx.dtype == jnp.int32
    monkeypatch.setenv("REPRO_DWT_CHECKED", "0")
    L.dwt_fwd(x, levels=1)  # off: silent (wrapping) compute, as ever
    monkeypatch.delenv("REPRO_DWT_CHECKED")
    L.dwt_fwd(x, levels=1)  # default: off


def test_disabled_path_never_traces(monkeypatch):
    """checked=False is one predicate: no interval machinery may run."""

    def boom(*a, **kw):  # noqa: ARG001
        raise AssertionError("trace ran on the disabled path")

    monkeypatch.setattr(ranges, "trace_forward", boom)
    monkeypatch.setattr(ranges, "_check_cascade", boom)
    x = _rand((2, 32), -4096, 4096, 6)
    for _name, roundtrip, shape in ENGINES:
        y = _rand(shape, -1024, 1024, 8)
        np.testing.assert_array_equal(
            np.asarray(roundtrip(y)), np.asarray(y)
        )


def test_overflow_error_is_typed():
    err = None
    try:
        L.dwt_fwd(jnp.full((1, 32), I32.max, jnp.int32), levels=1, checked=True)
    except IntegerOverflowError as e:
        err = e
    assert isinstance(err, OverflowError)
    assert isinstance(err, ResilienceError)
    assert "certified" in str(err) or "certificate" in str(err).lower() or (
        "range_certificate" in str(err)
    )


# ---------------------------------------------------------------------------
# Adversarial extremes: iinfo edges through every engine.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("val", [I32.min, I32.max])
@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("name,roundtrip,shape", ENGINES[:1] + ENGINES[3:4])
def test_extreme_int32_all_schemes_1d(val, scheme, name, roundtrip, shape):
    """iinfo edges x every scheme x oracle+fused 1D: bit-exact modular
    round-trip or the typed error — never a silent mismatch."""
    x = jnp.full((2, 32), val, jnp.int32)
    try:
        pyr = L.dwt_fwd(x, levels=2, scheme=scheme)
        xr = L.dwt_inv(pyr, scheme=scheme)
    except IntegerOverflowError:
        return
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


@pytest.mark.parametrize("val", [I32.min, I32.max])
@pytest.mark.parametrize("name,roundtrip,shape", ENGINES)
def test_extreme_int32_every_engine(val, name, roundtrip, shape):
    x = jnp.full(shape, val, jnp.int32)
    try:
        xr = roundtrip(x)
    except IntegerOverflowError:
        return
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))
    # and the checked variant must refuse the same input loudly
    with pytest.raises(IntegerOverflowError):
        roundtrip(x, checked=True)


@settings(max_examples=15, deadline=None)
@given(
    scheme=st.sampled_from(SCHEMES),
    val=st.sampled_from([I32.min, I32.max, I32.min + 1, I32.max - 1, 2**30]),
    seed=st.integers(0, 2**31 - 1),
)
def test_extreme_mixed_with_noise_differential(scheme, val, seed):
    """Differential vs the bigint-widened oracle: where the checked mode
    admits data near the edge, the engine result equals the exact
    (non-modular) transform; where it raises, wrapping was possible."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-1024, 1024, (1, 32)).astype(np.int64)
    x[0, rng.integers(0, 32)] = val
    xj = jnp.asarray(x, jnp.int32)
    try:
        pyr = L.dwt_fwd(xj, levels=1, scheme=scheme, checked=True)
    except IntegerOverflowError:
        data = ranges.Interval(int(x.min()), int(x.max()))
        ft = ranges.trace_forward(scheme, 1, data, mode="paper")
        assert ft.lo < I32.min or ft.hi > I32.max
        return
    # admitted: every band must match the exact object-dtype lifting
    ft = ranges.trace_forward(
        scheme,
        1,
        ranges.Interval(int(x.min()), int(x.max())),
        mode="paper",
    )
    assert I32.min <= ft.lo and ft.hi <= I32.max
    xr = L.dwt_inv(pyr, scheme=scheme, checked=True)
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(xj))


# ---------------------------------------------------------------------------
# Boundaries: codec, checkpoint, quantize, serve.
# ---------------------------------------------------------------------------


def test_codec_encode_checked_boundary():
    from repro.codec import container

    x = jnp.full((1, 64), 2**30, jnp.int32)
    wrapped = K.dwt_fwd(x, levels=2)
    with pytest.raises(IntegerOverflowError):
        container.encode_pyramid(wrapped, checked=True)
    assert isinstance(container.encode_pyramid(wrapped), bytes)  # off: as ever
    for scheme in SCHEMES:
        good = K.dwt_fwd(_rand((1, 64), -32767, 32767, 9), levels=2, scheme=scheme)
        blob = container.encode_pyramid(good, scheme=scheme, checked=True)
        assert container.decode_pyramid(blob).scheme == scheme


def test_ckpt_wz_quant_limit_certified():
    from repro.ckpt import checkpoint as CK

    # cdf53: the historical heuristic was already safe -> byte-identical
    assert CK._wz_quant_limit(4095.0, "cdf53", 2, 1) == 4095.0
    # 97m: the heuristic lied; the derived limit clamps it
    lim = CK._wz_quant_limit(4095.0, "97m", 2, 1)
    assert 1 <= lim < 4095.0
    bh = ranges.trace_forward(
        "97m", 2, ranges.Interval(-int(lim), int(lim)), mode="paper"
    ).band_hull()
    assert -32767 <= bh.lo and bh.hi <= 32767  # int16 pack provably safe


def test_ckpt_wz_97m_roundtrip_within_bound():
    from repro.ckpt import checkpoint as CK

    arr = np.random.default_rng(11).normal(size=(256,)).astype(np.float32)
    data, meta = CK._encode(arr, "wz", 2, scheme="97m")
    back = CK._decode(data, arr.shape, arr.dtype, "wz", meta)
    assert np.max(np.abs(back - arr)) <= meta["scale"] / 2 + 1e-6


def test_ckpt_wzrice_levels_capped_by_certificate():
    from repro.ckpt import checkpoint as CK

    arr = np.random.default_rng(12).normal(size=(8, 16, 16)).astype(np.float32)
    data, meta = CK._encode(arr, "wz-rice", 3, scheme="97m")
    cap = ranges.certified_levels(
        "97m", np.int32, (-32767, 32767), mode="paper", ndim=3
    )
    assert meta["levels"] <= max(1, cap)
    back = CK._decode(data, arr.shape, arr.dtype, "wz-rice", meta)
    assert np.max(np.abs(back - arr)) <= meta["scale"] / 2 + 1e-6
    # default scheme: cap far above the requested depth, nothing changes
    _, meta2 = CK._encode(arr, "wz-rice", 2, scheme="cdf53")
    assert meta2["levels"] == 2


def test_quantize_certificate_clamp():
    from repro.core import compression as C

    g = jnp.asarray(np.random.default_rng(13).normal(size=512), jnp.float32)
    s = C.tensor_scale(g)
    np.testing.assert_array_equal(
        np.asarray(C.quantize(g, s)),
        np.asarray(C.quantize(g, s, scheme="cdf53", levels=3)),
    )
    q = C.quantize(g, s, scheme="97m", levels=3, ndim=2, mode="jpeg2000")
    cert = ranges.range_certificate("97m", 3, np.int32, mode="jpeg2000", ndim=2)
    assert int(jnp.max(jnp.abs(q))) <= cert.hi


def test_serve_submit_range_admission():
    from repro.serve.serve_step import TransformRequest, WaveletServeEngine

    eng = WaveletServeEngine(
        height=16, width=16, batch_slots=2, levels=2, checked=True
    )
    # a spread interval (constant images trace as degenerate, hence safe)
    hot = np.full((16, 16), 2**29, np.int32)
    hot[::2] = -(2**29)
    with pytest.raises(IntegerOverflowError):
        eng.submit(TransformRequest(uid=0, image=hot))
    assert eng.scheduler.pending() == 0  # shed synchronously, nothing queued
    good = TransformRequest(
        uid=1,
        image=np.random.default_rng(14)
        .integers(-4096, 4096, (16, 16))
        .astype(np.int32),
    )
    eng.submit(good)
    (served,) = eng.run([])
    assert served.done and served.pyramid is not None
    # unchecked engine admits the same hot request (historic behavior)
    eng2 = WaveletServeEngine(height=16, width=16, batch_slots=2, levels=2)
    eng2.submit(TransformRequest(uid=2, image=hot))
    assert eng2.scheduler.pending() == 1


# ---------------------------------------------------------------------------
# Chaos-lane variants: checked mode under the fault-injection invariant
# (typed error or bit-exact — never silent corruption).
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_chaos_checked_env_forces_typed_errors(monkeypatch):
    monkeypatch.setenv("REPRO_DWT_CHECKED", "1")
    hot = jnp.full((2, 16, 16), I32.max, jnp.int32)
    for fwd in (
        lambda: L.dwt_fwd_2d_multi(hot, levels=2),
        lambda: K.dwt_fwd_2d_multi(hot, levels=2),
        lambda: K.dwt_fwd_nd(jnp.full((8, 8, 8), I32.max, jnp.int32), levels=1, ndim=3),
    ):
        with pytest.raises(IntegerOverflowError):
            fwd()
    # and certified traffic flows untouched under the same env
    ok = _rand((2, 16, 16), -4096, 4096, 15)
    pyr = K.dwt_fwd_2d_multi(ok, levels=2)
    np.testing.assert_array_equal(
        np.asarray(K.dwt_inv_2d_multi(pyr)), np.asarray(ok)
    )


@pytest.mark.chaos
def test_chaos_checked_serve_sheds_not_corrupts(monkeypatch):
    from repro.serve.serve_step import TransformRequest, WaveletServeEngine

    monkeypatch.setenv("REPRO_DWT_CHECKED", "1")
    eng = WaveletServeEngine(height=16, width=16, batch_slots=2, levels=2)
    hot = np.full((16, 16), 2**29, np.int32)
    hot[::2] = -(2**29)
    with pytest.raises(IntegerOverflowError):
        eng.submit(TransformRequest(uid=0, image=hot))
