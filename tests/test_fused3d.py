"""Fused N-D engine (kernels/fused3d.py): bit-exactness vs the oracle on
every path — whole-volume Pallas kernel, depth-slab kernel, XLA
reference — for every registered scheme, both rounding modes, odd and
degenerate shapes, batched lead dims, and the ndim=1/2 re-wrapping."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import kernels as K
from repro.core import lifting as L
from repro.kernels import backend as B
from repro.kernels import fused3d

RNG = np.random.default_rng(11)
SCHEMES = K.available_schemes()


def _vol(*shape):
    return jnp.asarray(RNG.integers(-2048, 2048, shape), jnp.int32)


def _assert_pyr_equal(got: L.PyramidND, want: L.PyramidND):
    np.testing.assert_array_equal(np.asarray(got.approx), np.asarray(want.approx))
    assert len(got.details) == len(want.details)
    for lvl_g, lvl_w in zip(got.details, want.details):
        assert len(lvl_g) == len(lvl_w)
        for bg, bw in zip(lvl_g, lvl_w):
            np.testing.assert_array_equal(np.asarray(bg), np.asarray(bw))


@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize(
    "shape", [(2, 2, 2), (3, 3, 3), (2, 3, 4), (5, 6, 7), (8, 8, 8)]
)
def test_roundtrip_matches_reference(shape, scheme):
    """Default-backend fwd matches the oracle; inverse restores exactly."""
    x = _vol(*shape)
    levels = L.max_levels_nd(shape)
    for mode in ("paper", "jpeg2000"):
        want = L.dwt_fwd_nd(x, levels=levels, mode=mode, scheme=scheme, ndim=3)
        got = K.dwt_fwd_nd(x, levels=levels, mode=mode, scheme=scheme, ndim=3)
        _assert_pyr_equal(got, want)
        xr = K.dwt_inv_nd(got, mode=mode, scheme=scheme)
        np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


@pytest.mark.parametrize("shape", [(1, 4, 4), (4, 1, 4), (4, 4, 1), (1, 1, 1)])
def test_degenerate_axes_identity_pyramid(shape):
    """An axis of length 1 admits no level: max_levels_nd is 0 and the
    levels=0 pyramid round-trips as the identity (no crash)."""
    assert L.max_levels_nd(shape) == 0
    x = _vol(*shape)
    pyr = K.dwt_fwd_nd(x, levels=0, ndim=3)
    assert pyr.details == ()
    np.testing.assert_array_equal(
        np.asarray(K.dwt_inv_nd(pyr)), np.asarray(x)
    )
    with pytest.raises(ValueError, match="too small"):
        K.dwt_fwd_nd(x, levels=1, ndim=3)


@pytest.mark.parametrize("scheme", SCHEMES)
def test_interpret_whole_volume_kernel(scheme):
    """backend="interpret" runs the whole-volume Pallas kernel body."""
    x = _vol(4, 6, 8)
    want = L.dwt_fwd_nd(x, levels=1, scheme=scheme, ndim=3)
    got = K.dwt_fwd_nd(x, levels=1, scheme=scheme, ndim=3, backend="interpret")
    _assert_pyr_equal(got, want)
    xr = K.dwt_inv_nd(got, scheme=scheme, backend="interpret")
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("shape", [(8, 5, 6), (9, 4, 4), (12, 6, 5)])
def test_forced_slab_path(monkeypatch, scheme, shape):
    """REPRO_DWT_SLAB forces the depth-slab kernel on small volumes (the
    multi-slab grid lever); schemes that cannot window the depth axis
    (cdf22 anywhere, haar on odd depth) stay whole-volume — either way
    the result is bit-exact vs the oracle."""
    monkeypatch.setenv("REPRO_DWT_SLAB", "4")
    plan = fused3d.plan_3d(*shape, backend="interpret", scheme=scheme)
    can_window_depth = K.get_scheme(scheme).can_window(shape[0])
    assert plan == (
        "slab-interpret" if can_window_depth else "whole-interpret"
    ), plan
    x = _vol(*shape)
    for mode in ("paper", "jpeg2000"):
        want = L.dwt_fwd_nd(x, levels=2, mode=mode, scheme=scheme, ndim=3)
        got = K.dwt_fwd_nd(
            x, levels=2, mode=mode, scheme=scheme, ndim=3, backend="interpret"
        )
        _assert_pyr_equal(got, want)
        xr = K.dwt_inv_nd(got, mode=mode, scheme=scheme, backend="interpret")
        np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


# (scheme, (D, H, W)) volumes whose plane is over a 0.01 MiB budget (the
# floored 4096 samples) even in the smallest slab of whole planes, so the
# slab kernel tiles H as well; odd D, H and W, and one shape (9, 37, 21)
# with three slabs of three H tiles (haar windows even axes only)
PLANE_TILED = [
    ("cdf53", (9, 37, 21)),
    ("cdf53", (7, 50, 19)),
    ("97m", (12, 40, 16)),
    ("97m", (7, 41, 17)),
    ("haar", (8, 64, 37)),
]


@pytest.mark.parametrize("scheme,shape", PLANE_TILED)
def test_plane_tiled_slab_path(monkeypatch, scheme, shape):
    """A small REPRO_DWT_VMEM_MB puts the plane over the smallest slab of
    whole planes: the slab kernel tiles H too (windows of TD + 2*halo
    slices by TH + 2*halo rows, W whole), and forward and inverse stay
    bit-exact vs the oracle in both modes."""
    monkeypatch.setenv("REPRO_DWT_VMEM_MB", "0.01")
    d, h, w = shape
    sch = K.get_scheme(scheme)
    assert d * h * w > B.fused3d_budget_elems()
    assert (2 + 2 * sch.halo) * h * w > B.fused3d_budget_elems()
    td, th = B.pick_slab(d, h, w, sch.halo, tile_h=sch.can_window(h))
    assert th is not None and th % B.SLAB_TILE_ROWS == 0
    assert fused3d.plan_3d(*shape, backend="interpret", scheme=scheme) == (
        "slab-interpret"
    )
    x = _vol(2, *shape)
    for mode in ("paper", "jpeg2000"):
        want = L.dwt_fwd_nd(x, levels=1, mode=mode, scheme=scheme, ndim=3)
        got = K.dwt_fwd_nd(
            x, levels=1, mode=mode, scheme=scheme, ndim=3, backend="interpret"
        )
        _assert_pyr_equal(got, want)
        np.testing.assert_array_equal(
            np.asarray(L.dwt_inv_nd(want, mode=mode, scheme=scheme)),
            np.asarray(x),
        )
        xr = K.dwt_inv_nd(want, mode=mode, scheme=scheme, backend="interpret")
        np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


def test_ndim_routing_matches_existing_engines():
    """ndim=1/2 reuse the fused 1D/2D engines; the PyramidND wrapping
    must agree band-for-band with the oracle's code order."""
    x2 = _vol(12, 14)
    got2 = K.dwt_fwd_nd(x2, levels=2, ndim=2)
    _assert_pyr_equal(got2, L.dwt_fwd_nd(x2, levels=2, ndim=2))
    np.testing.assert_array_equal(np.asarray(K.dwt_inv_nd(got2)), np.asarray(x2))

    x1 = _vol(64)
    got1 = K.dwt_fwd_nd(x1, levels=3, ndim=1)
    _assert_pyr_equal(got1, L.dwt_fwd_nd(x1, levels=3, ndim=1))
    np.testing.assert_array_equal(np.asarray(K.dwt_inv_nd(got1)), np.asarray(x1))

    x4 = _vol(4, 4, 4, 4)
    got4 = K.dwt_fwd_nd(x4, levels=1, ndim=4)
    _assert_pyr_equal(got4, L.dwt_fwd_nd(x4, levels=1, ndim=4))
    np.testing.assert_array_equal(np.asarray(K.dwt_inv_nd(got4)), np.asarray(x4))


def test_batched_lead_dims_map_to_grid():
    x = _vol(3, 6, 8, 8)  # (batch, D, H, W)
    got = K.dwt_fwd_nd(x, levels=2, ndim=3)
    _assert_pyr_equal(got, L.dwt_fwd_nd(x, levels=2, ndim=3))
    np.testing.assert_array_equal(np.asarray(K.dwt_inv_nd(got)), np.asarray(x))


def test_narrow_dtypes_promote():
    """int8/int16 volumes compute in int32 (no silent wraparound)."""
    for dtype in (jnp.int8, jnp.int16):
        x = jnp.asarray(RNG.integers(100, 124, (4, 4, 4)), dtype)
        got = K.dwt_fwd_nd(x, levels=1, ndim=3)
        _assert_pyr_equal(got, L.dwt_fwd_nd(x, levels=1, ndim=3))
        np.testing.assert_array_equal(
            np.asarray(K.dwt_inv_nd(got)), np.asarray(x, np.int32)
        )


def test_pack_unpack_nd_roundtrip():
    shape = (5, 6, 7)
    x = _vol(*shape)
    pyr = K.dwt_fwd_nd(x, levels=2, ndim=3)
    flat = K.pack_nd(pyr)
    assert flat.shape == (5 * 6 * 7,)
    back = K.unpack_nd(flat, shape, 2)
    _assert_pyr_equal(back, pyr)
    # levels=0 needs an explicit ndim (no bands to derive it from)
    p0 = K.dwt_fwd_nd(x, levels=0, ndim=3)
    with pytest.raises(ValueError, match="ndim"):
        K.pack_nd(p0)
    np.testing.assert_array_equal(
        np.asarray(K.unpack_nd(K.pack_nd(p0, ndim=3), shape, 0).approx),
        np.asarray(p0.approx),
    )


def test_band_shapes_nd_matches_transform():
    shape = (6, 7, 9)
    a_shape, det_shapes = K.band_shapes_nd(shape, 2)
    pyr = K.dwt_fwd_nd(_vol(*shape), levels=2, ndim=3)
    assert tuple(pyr.approx.shape) == a_shape
    for lvl, want_lvl in zip(pyr.details, det_shapes):
        assert tuple(tuple(b.shape) for b in lvl) == want_lvl


def test_max_levels_nd_loops_are_safe():
    for shape in [(1, 8, 8), (2, 2, 2), (3, 5, 9), (16, 16, 16)]:
        lv = K.max_levels_nd(shape)
        pyr = K.dwt_fwd_nd(_vol(*shape), levels=lv, ndim=3)  # must not raise
        assert pyr.levels == lv


def test_inv_rejects_malformed_pyramid():
    # odd dims: the detail bands have distinct shapes, so swapping in a
    # wrong-shaped band is detectable (on even dims all octants coincide)
    pyr = K.dwt_fwd_nd(_vol(5, 6, 7), levels=1, ndim=3)
    bad = L.PyramidND(
        approx=pyr.approx,
        details=((pyr.details[0][0],) * 7,),  # every band shaped like code 1
    )
    with pytest.raises(ValueError, match="band shape mismatch"):
        K.dwt_inv_nd(bad)
    short = L.PyramidND(approx=pyr.approx, details=(pyr.details[0][:5],))
    with pytest.raises(ValueError):
        K.dwt_inv_nd(short)


def test_plan_3d_names_paths(monkeypatch):
    """plan_3d mirrors plan_2d: explicit pallas requests degrade to
    interpret off-accelerator, tiny budgets force the slab path, and
    un-slab-able volumes past the budget name the xla cliff."""
    assert fused3d.plan_3d(4, 8, 8, backend="xla") == "xla"
    assert fused3d.plan_3d(4, 8, 8, backend="pallas").endswith(
        "-pallas" if K.has_compiled_pallas() else "-interpret"
    )
    monkeypatch.setenv("REPRO_DWT_VMEM_MB", "0.01")
    # 17x16x16 = 4352 elems exceeds the floored 4096-elem budget -> must
    # leave whole-volume; cdf53 can slab the depth axis, cdf22 cannot
    # (antisymmetric lift is unwindowable) -> the named xla cliff
    kind = "pallas" if K.has_compiled_pallas() else "interpret"
    assert (
        fused3d.plan_3d(17, 16, 16, backend="pallas", scheme="cdf53")
        == f"slab-{kind}"
    )
    assert fused3d.plan_3d(17, 16, 16, backend="pallas", scheme="cdf22") == "xla"
    # a plane over the budget is tiled along H only where the scheme can
    # window H: haar cannot on odd H, so that volume stays on the cliff
    assert fused3d.plan_3d(8, 64, 37, backend="pallas", scheme="haar") == (
        f"slab-{kind}"
    )
    assert fused3d.plan_3d(8, 63, 37, backend="pallas", scheme="haar") == "xla"


def test_levels_validation():
    x = _vol(4, 4, 4)
    with pytest.raises(ValueError):
        K.dwt_fwd_nd(x, levels=-1, ndim=3)
    with pytest.raises(ValueError):
        K.dwt_fwd_nd(x, levels=1, ndim=0)
    with pytest.raises(ValueError):
        K.dwt_fwd_nd(_vol(4, 4), levels=1, ndim=3)  # too few axes
