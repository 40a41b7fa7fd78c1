"""Tiled halo-window 2D engine: bit-exactness at every tile boundary.

Property tests (hypothesis, or the deterministic shim off-container)
sweep odd/even heights and widths, tile-edge-straddling sizes, both
rounding modes, and multiple levels against the ``kernels/ref`` oracle —
the tiled kernels must be indistinguishable from the whole-image math.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import lifting
from repro.kernels import ref, tiled2d

RNG = np.random.default_rng(37)


def _img(h, w, lead=()):
    return jnp.asarray(RNG.integers(-1000, 1000, lead + (h, w)), jnp.int32)


def _assert_bands_equal(got, want):
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


@settings(max_examples=12, deadline=None)
@given(
    h=st.integers(min_value=3, max_value=40),
    w=st.integers(min_value=3, max_value=40),
    th=st.sampled_from([4, 6, 8, 16]),
    tw=st.sampled_from([4, 6, 8, 16]),
    mode=st.sampled_from(["paper", "jpeg2000"]),
)
def test_fwd_tiled_matches_ref_property(h, w, th, tw, mode):
    x = _img(h, w, lead=(1,))
    ll, lh, hl, hh = tiled2d.fwd2d_tiled(x, mode, th, tw, True)
    want = ref.dwt53_fwd_2d(x, mode=mode)
    _assert_bands_equal((ll, lh, hl, hh), (want.ll, want.lh, want.hl, want.hh))


@settings(max_examples=12, deadline=None)
@given(
    h=st.integers(min_value=3, max_value=40),
    w=st.integers(min_value=3, max_value=40),
    th=st.sampled_from([4, 8, 16]),
    tw=st.sampled_from([4, 8, 16]),
    mode=st.sampled_from(["paper", "jpeg2000"]),
)
def test_inv_tiled_roundtrip_property(h, w, th, tw, mode):
    x = _img(h, w, lead=(1,))
    bands = ref.dwt53_fwd_2d(x, mode=mode)
    xr = tiled2d.inv2d_tiled(
        bands.ll, bands.lh, bands.hl, bands.hh, mode, th, tw, True
    )
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


@pytest.mark.parametrize("mode", ["paper", "jpeg2000"])
@pytest.mark.parametrize(
    "hw",
    [
        # tile-edge-straddling sizes around an (8, 8) tile grid
        (7, 8), (8, 7), (8, 8), (9, 8), (8, 9), (15, 17), (16, 16),
        (17, 15), (23, 25),
    ],
)
def test_tile_edge_straddles(hw, mode):
    h, w = hw
    x = _img(h, w, lead=(2,))
    ll, lh, hl, hh = tiled2d.fwd2d_tiled(x, mode, 8, 8, True)
    want = ref.dwt53_fwd_2d(x, mode=mode)
    _assert_bands_equal((ll, lh, hl, hh), (want.ll, want.lh, want.hl, want.hh))
    xr = tiled2d.inv2d_tiled(ll, lh, hl, hh, mode, 8, 8, True)
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


def test_multi_level_tiled_via_env(monkeypatch):
    """REPRO_DWT_TILE forces the tiled path through the public pyramid."""
    from repro import kernels as K

    monkeypatch.setenv("REPRO_DWT_TILE", "8")
    x = _img(37, 41)
    for levels in (1, 2, 3):
        pyr = K.dwt53_fwd_2d_multi(x, levels=levels, backend="interpret")
        want = lifting.dwt53_fwd_2d_multi(x, levels=levels)
        np.testing.assert_array_equal(np.asarray(pyr.ll), np.asarray(want.ll))
        for got_lvl, want_lvl in zip(pyr.details, want.details):
            _assert_bands_equal(got_lvl, want_lvl)
        xr = K.dwt53_inv_2d_multi(pyr, backend="interpret")
        np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


def test_batched_grid_mapping():
    """Leading batch dims map to grid cells and stay bit-exact."""
    x = _img(20, 24, lead=(3,))
    ll, lh, hl, hh = tiled2d.fwd2d_tiled(x, "paper", 8, 8, True)
    want = ref.dwt53_fwd_2d(x)
    _assert_bands_equal((ll, lh, hl, hh), (want.ll, want.lh, want.hl, want.hh))
    xr = tiled2d.inv2d_tiled(ll, lh, hl, hh, "paper", 8, 8, True)
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


@pytest.mark.slow
def test_2048_runs_tiled_engine_end_to_end():
    """The acceptance shape: 2048x2048 exceeds every whole-image VMEM
    budget, stays on the Pallas engine (tiled), and is bit-exact."""
    from repro import kernels as K
    from repro.kernels import fused2d

    plan = fused2d.plan_2d(2048, 2048, backend="pallas")
    assert plan.startswith("tiled-"), plan  # tiled-pallas on accelerators
    x = jnp.asarray(RNG.integers(-2048, 2048, (2048, 2048)), jnp.int32)
    bands = K.dwt53_fwd_2d(x, backend="interpret")
    want = ref.dwt53_fwd_2d(x)
    np.testing.assert_array_equal(np.asarray(bands.ll), np.asarray(want.ll))
    np.testing.assert_array_equal(np.asarray(bands.hh), np.asarray(want.hh))
    xr = K.dwt53_inv_2d(bands, backend="interpret")
    np.testing.assert_array_equal(np.asarray(xr), np.asarray(x))


def test_interior_math_helpers_match_reference_1d():
    """_fwd_axis_ext on a reflect-padded row == the 1D reference."""
    for n in (6, 7, 9, 16, 33):
        x = jnp.asarray(RNG.integers(-500, 500, (4, n)), jnp.int32)
        xe = jnp.pad(x, ((0, 0), (2, 2)), mode="reflect")
        if xe.shape[-1] % 2:
            xe = jnp.pad(xe, ((0, 0), (0, 1)), mode="edge")
        s, d = tiled2d._fwd_axis_ext(xe, -1, "paper")
        ws, wd = ref.dwt53_fwd_1d(x)
        np.testing.assert_array_equal(
            np.asarray(s[..., : ws.shape[-1]]), np.asarray(ws)
        )
        np.testing.assert_array_equal(
            np.asarray(d[..., : wd.shape[-1]]), np.asarray(wd)
        )
