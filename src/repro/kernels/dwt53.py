"""Pallas TPU kernels for the integer lifting DWT — any registered scheme.

TPU adaptation of the paper's PE (see DESIGN.md §2): the serial
delay-line dataflow becomes a blocked-parallel VPU computation over
halo'd windows.  Each grid cell holds one ``(block_rows, window)`` slice
of the signal's even and odd streams (forward) or of the two bands
(inverse) in VMEM and runs the scheme's full lifting cascade as
interior-only math (``schemes.lift_fwd_split`` / ``lift_inv_split``) —
integer adds/subtracts and arithmetic shifts only (multiplierless).  The
even/odd split and merge run in the XLA wrapper: Mosaic has no layout
for them in-kernel.

Cross-tile dependencies (the paper's programmable delays) are resolved
by OVERLAP, not sequencing: the wrapper (``ops.py``) gathers each cell's
window through whole-point-reflected index maps
(``schemes.reflect_indices``), so neighboring windows share
``scheme.halo`` samples (forward) / ``scheme.inv_margin`` band pairs
(inverse) and every cell recomputes its fringe locally.  Tiles stay
embarrassingly parallel, and the halo width is DERIVED from the scheme's
step supports — the seed's hard-coded one-column (5,3) halos are just
``cdf53``'s instance.

The module keeps its historical ``dwt53.py`` name (the (5,3) is still
the flagship scheme); the kernels themselves are scheme-parameterized
via a static argument.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
from jax.experimental import pallas as pl

from repro.core import schemes as S

# re-exported defaults (historical import site for the block geometry)
from repro.kernels.backend import DEFAULT_BLOCK_PAIRS, DEFAULT_BLOCK_ROWS  # noqa: F401


def _fwd_kernel(e_ref, o_ref, s_ref, d_ref, *, scheme, mode: str):
    """Forward lifting cascade over one halo'd window tile."""
    s_ref[...], d_ref[...] = S.lift_fwd_split(
        [e_ref[0], o_ref[0]], scheme, mode, (None,)
    )


def _inv_kernel(s_ref, d_ref, e_ref, o_ref, *, scheme, mode: str):
    """Inverse lifting cascade over one pair of margin-extended band tiles."""
    e_ref[...], o_ref[...] = S.lift_inv_split(
        [s_ref[0], d_ref[0]], scheme, mode, (None,)
    )


def _specs(block_rows: int, wlen: int, block_pairs: int):
    """Window blocks (tile-major: the tile index never sits in the two
    minor dims Mosaic tiles) and natural-layout output blocks."""
    win = pl.BlockSpec((1, block_rows, wlen), lambda r, t: (t, r, 0))
    out = pl.BlockSpec((block_rows, block_pairs), lambda r, t: (r, t))
    return win, out


@functools.partial(
    jax.jit,
    static_argnames=("scheme", "mode", "block_rows", "block_pairs", "interpret"),
)
def lift_fwd_windows(
    e_wins: jax.Array,
    o_wins: jax.Array,
    *,
    scheme: str,
    mode: str,
    block_rows: int,
    block_pairs: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Forward lifting over gathered polyphase signal windows.

    e_wins, o_wins : (n_tiles, rows, block_pairs + 2*fwd_margin) — the
           even / odd samples of each window, which carries the scheme's
           reflect halo on both sides; rows % block_rows == 0.
    returns (s, d) : (rows, n_tiles * block_pairs) each — the core output
           pairs of every window, in signal order.
    """
    n_tiles, rows, wlen = e_wins.shape
    win_spec, out_spec = _specs(block_rows, wlen, block_pairs)
    out = jax.ShapeDtypeStruct((rows, n_tiles * block_pairs), e_wins.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scheme=scheme, mode=mode),
        grid=(rows // block_rows, n_tiles),
        in_specs=[win_spec, win_spec],
        out_specs=(out_spec, out_spec),
        out_shape=(out, out),
        interpret=interpret,
    )(e_wins, o_wins)


@functools.partial(
    jax.jit,
    static_argnames=("scheme", "mode", "block_rows", "block_pairs", "interpret"),
)
def lift_inv_windows(
    s_wins: jax.Array,
    d_wins: jax.Array,
    *,
    scheme: str,
    mode: str,
    block_rows: int,
    block_pairs: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Inverse lifting over gathered band windows.

    s_wins, d_wins : (n_tiles, rows, block_pairs + 2*inv_margin) — band
           entries extended by the scheme's inverse margin per side.
    returns (even, odd) : (rows, n_tiles * block_pairs) each — the core
           polyphase samples, for the wrapper to interleave.
    """
    n_tiles, rows, wlen = s_wins.shape
    win_spec, out_spec = _specs(block_rows, wlen, block_pairs)
    out = jax.ShapeDtypeStruct((rows, n_tiles * block_pairs), s_wins.dtype)
    return pl.pallas_call(
        functools.partial(_inv_kernel, scheme=scheme, mode=mode),
        grid=(rows // block_rows, n_tiles),
        in_specs=[win_spec, win_spec],
        out_specs=(out_spec, out_spec),
        out_shape=(out, out),
        interpret=interpret,
    )(s_wins, d_wins)
