"""Tiled halo-exchange 2D integer lifting DWT — Pallas at any image size.

The whole-image fused kernel (``kernels/fused2d.py``) needs ~6 image-sized
buffers resident in VMEM, which caps the images it can take; everything
larger used to fall off a cliff onto the XLA path.  This module removes
the cliff with the paper's own parallel-lifting structure: polyphase PEs
need only a small boundary overlap — ``scheme.halo`` samples, DERIVED
from the scheme's step supports (2 for the paper's cdf53, 4 for 97m, 0
for haar) — so the image is blocked into ``(TH, TW)`` core tiles, each
extended by the scheme's halo on every side, and a Pallas grid sweeps
``(batch, tile_row, tile_col)`` cells.  The grid pipeline streams one
halo'd window per cell HBM->VMEM (Pallas double-buffers blocked
operands: the next cell's DMA overlaps this cell's compute), runs the
scheme's full row+column lifting cascade on the resident window, and
writes the four subband tiles.

Correctness rests on one identity (validated by the tier-1 sweeps): for
schemes whose steps commute with whole-point reflection
(``scheme.symmetric`` — the registry's cdf53 and 97m; haar qualifies on
even dims because it reads no extension at all), the reference's entire
boundary policy IS whole-point reflect extension of the *input*.  The
windows are therefore gathered through reflected index maps
(``schemes.reflect_indices`` forward, ``schemes.reflect_entries`` for
the band windows of the inverse), every window entry is an exact
extension value, and every tile runs the same interior-only lifting math
with no boundary special cases inside the kernel:

  forward : window (TH + 2*halo, TW + 2*halo) -> LL/LH/HL/HH (TH/2, TW/2)
  inverse : band windows (TH/2 + 2*m, TW/2 + 2*m), m = scheme.inv_margin
            -> image tile (TH, TW)

Mosaic cannot lay out an in-kernel even/odd split of the lane axis, so
the window gather itself splits: each window arrives as its four
polyphase components (the index maps taken at stride 2 — windows start
on an even sample because the halo is ``2 * fwd_margin``), the kernel
runs ``schemes.lift_fwd_split`` / ``lift_inv_split`` on them, and the
inverse returns the tile's four polyphase components for the wrapper to
interleave.  The default 256-sample core tile makes every band tile a
(128, 128) block — (8, 128)-aligned, as Mosaic requires of a block that
is not the whole array.

Tile selection (``backend.pick_tile``) derives from the queried device
memory budget and the scheme's halo; ``REPRO_DWT_TILE`` overrides.  See
DESIGN.md §6 and §9.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import schemes as S

Array = jax.Array


# back-compat alias (cdf53 instance of the generic interior math; the
# tier-1 identity test drives it directly)
def _fwd_axis_ext(x: Array, axis: int, mode: str) -> Tuple[Array, Array]:
    return S.lift_fwd_axis_ext(x, "cdf53", axis=axis, mode=mode)


# ---------------------------------------------------------------------------
# Window gathering: trace-time reflected index maps, XLA gather.
# ---------------------------------------------------------------------------


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _win_rows(n_tiles: int, core: int, ext: int, idx_fn) -> np.ndarray:
    """(n_tiles, core + 2*ext) index rows for stride-``core`` windows."""
    return np.stack([idx_fn(t * core - ext, core + 2 * ext) for t in range(n_tiles)])


def _gather2d(x: Array, rows: np.ndarray, cols: np.ndarray) -> Array:
    """(B, H', W') -> (B, n_th, n_tw, wh, ww) overlapping windows."""
    win = x[:, rows][:, :, :, cols]  # (B, n_th, wh, n_tw, ww)
    return jnp.transpose(win, (0, 1, 3, 2, 4))


# ---------------------------------------------------------------------------
# Pallas kernels: one grid cell = one halo'd window, resident in VMEM.
# Refs and results are in band-code order (bit 0 = odd / highpass along
# the width, bit 1 along the height): ll, hl, lh, hh.
# ---------------------------------------------------------------------------


def _fwd_tile_kernel(*refs, scheme, mode: str):
    win_refs, band_refs = refs[:4], refs[4:]
    bands = S.lift_fwd_split(
        [r[0, 0, 0] for r in win_refs], scheme, mode, (None, None)
    )
    for ref, b in zip(band_refs, bands):
        ref[0] = b


def _inv_tile_kernel(*refs, scheme, mode: str):
    band_refs, comp_refs = refs[:4], refs[4:]
    comps = S.lift_inv_split(
        [r[0, 0, 0] for r in band_refs], scheme, mode, (None, None)
    )
    for ref, c in zip(comp_refs, comps):
        ref[0] = c


def _win_spec(wh: int, ww: int):
    """One (1,1,1,wh,ww) window per (b, i, j) grid cell."""
    return pl.BlockSpec((1, 1, 1, wh, ww), lambda b, i, j: (b, i, j, 0, 0))


def _out_spec(bh: int, bw: int):
    """A (1,bh,bw) tile of a (B, n_th*bh, n_tw*bw) output per cell."""
    return pl.BlockSpec((1, bh, bw), lambda b, i, j: (b, i, j))


@functools.partial(
    jax.jit, static_argnames=("scheme", "mode", "th", "tw", "interpret")
)
def fwd2d_tiled(
    x: Array, mode: str, th: int, tw: int, interpret: bool,
    scheme: str = "cdf53",
):
    """Tiled forward 2D level over a (B, H, W) batch.

    Returns (ll, lh, hl, hh) with the reference band shapes.  Bit-exact
    vs ``core.lifting.dwt_fwd_2d`` for every scheme/shape the dispatcher
    routes here (``scheme.can_window`` along both dims) — the tier-1
    property sweep asserts it.
    """
    sch = S.get_scheme(scheme)
    halo = sch.halo
    m = sch.fwd_margin
    bsz, h, w = x.shape
    h_e, w_e = h - h // 2, w - w // 2
    h_o, w_o = h // 2, w // 2
    bh, bw = th // 2, tw // 2
    n_th = _ceil_to(h_e, bh) // bh
    n_tw = _ceil_to(w_e, bw) // bw
    rows = _win_rows(n_th, th, halo, lambda s, c: S.reflect_indices(s, c, h))
    cols = _win_rows(n_tw, tw, halo, lambda s, c: S.reflect_indices(s, c, w))
    comps = [
        _gather2d(x, rows[:, (code >> 1) & 1 :: 2], cols[:, code & 1 :: 2])
        for code in range(4)
    ]
    out = jax.ShapeDtypeStruct((bsz, n_th * bh, n_tw * bw), x.dtype)
    ll, hl, lh, hh = pl.pallas_call(
        functools.partial(_fwd_tile_kernel, scheme=sch, mode=mode),
        grid=(bsz, n_th, n_tw),
        in_specs=[_win_spec(bh + 2 * m, bw + 2 * m)] * 4,
        out_specs=(_out_spec(bh, bw),) * 4,
        out_shape=(out,) * 4,
        interpret=interpret,
    )(*comps)
    return (
        ll[:, :h_e, :w_e],
        lh[:, :h_o, :w_e],
        hl[:, :h_e, :w_o],
        hh[:, :h_o, :w_o],
    )


@functools.partial(
    jax.jit, static_argnames=("scheme", "mode", "th", "tw", "interpret")
)
def inv2d_tiled(
    ll: Array, lh: Array, hl: Array, hh: Array,
    mode: str, th: int, tw: int, interpret: bool,
    scheme: str = "cdf53",
):
    """Tiled inverse of :func:`fwd2d_tiled` over (B, ...) band batches."""
    sch = S.get_scheme(scheme)
    m = sch.inv_margin
    bsz = ll.shape[0]
    h = ll.shape[-2] + lh.shape[-2]
    w = ll.shape[-1] + hl.shape[-1]
    h_e, w_e = ll.shape[-2], ll.shape[-1]
    me, mo = th // 2, tw // 2
    n_th = _ceil_to(h_e, me) // me
    n_tw = _ceil_to(w_e, mo) // mo
    # band-entry window maps per (axis, polyphase role): rows of ll/hl are
    # even-role over H, rows of lh/hh odd-role; columns of ll/lh are
    # even-role over W, columns of hl/hh odd-role.  Every window entry is
    # an exact policy extension value (schemes.reflect_entries), which
    # subsumes the seed's grow/edge/whole-point special cases.
    r_s = _win_rows(n_th, me, m, lambda s, c: S.reflect_entries(s, c, 0, h))
    r_d = _win_rows(n_th, me, m, lambda s, c: S.reflect_entries(s, c, 1, h))
    c_s = _win_rows(n_tw, mo, m, lambda s, c: S.reflect_entries(s, c, 0, w))
    c_d = _win_rows(n_tw, mo, m, lambda s, c: S.reflect_entries(s, c, 1, w))
    wins = (
        _gather2d(ll, r_s, c_s),
        _gather2d(hl, r_s, c_d),
        _gather2d(lh, r_d, c_s),
        _gather2d(hh, r_d, c_d),
    )
    comp = jax.ShapeDtypeStruct((bsz, n_th * me, n_tw * mo), ll.dtype)
    comps = pl.pallas_call(
        functools.partial(_inv_tile_kernel, scheme=sch, mode=mode),
        grid=(bsz, n_th, n_tw),
        in_specs=[_win_spec(me + 2 * m, mo + 2 * m)] * 4,
        out_specs=(_out_spec(me, mo),) * 4,
        out_shape=(comp,) * 4,
        interpret=interpret,
    )(*wins)
    x = S.polyphase_merge(list(comps), (n_th * th, n_tw * tw))
    return x[:, :h, :w]
