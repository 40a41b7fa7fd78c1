"""Public wrappers around the Pallas lifting kernels, with
compiled-by-default backend dispatch (see ``kernels/backend.py``) and
scheme parameterization (see ``core/schemes.py``).

Handles everything the kernel keeps out of VMEM: window gathering
through whole-point-reflected index maps (the boundary policy — halo
width derived from ``scheme.halo`` instead of the seed's hard-coded
(5,3) columns), arbitrary lengths (odd lengths, non powers of two — an
explicit paper claim), dtype promotion (int8/int16 inputs are computed
in int32: narrow-dtype lifting sums used to wrap silently, destroying
the band statistics the int8 quantizer downstream relies on), and
multi-level recursion.

Every public function takes ``backend=None`` and resolves it through
``backend.resolve``: ``pallas`` (compiled kernels, TPU default),
``xla`` (the jnp reference under jit, CPU/GPU default), or ``interpret``
(Pallas emulator, debugging only).  The multi-level entry points
(``dwt_fwd`` / ``dwt_inv``) are FUSED: all levels trace into one
compiled computation, the batch flattening / dtype promotion / row
padding happen once, and the polyphase streams stay device-resident
between levels instead of round-tripping through a per-level dispatch
(DESIGN.md §4).

Schemes whose steps do not commute with whole-point reflection (e.g.
``cdf22``'s antisymmetric gradient lift) cannot run the windowed kernel
dataflow; on the kernel backends they fall back to the in-graph
band-policy math inside the same jitted dispatch — still compiled,
still bit-exact (the same precedent as the small-signal fallback).

Bit-exactness contract: for every scheme/shape/dtype/mode and every
backend these wrappers return exactly what `kernels.ref` (==
`core.lifting`) returns. Tests sweep this.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lifting as _lift
from repro.core import ranges as _ranges
from repro.core import schemes as S
from repro.core.lifting import WaveletPyramid, _check_mode
from repro.kernels import backend as _backend
from repro.kernels import dwt53 as _k

# below this many pairs the kernel grid degenerates; use the jnp reference
_MIN_KERNEL_PAIRS = 8


def _compute_dtype(dtype) -> jnp.dtype:
    """Narrow ints (signed or unsigned) promote to int32: the lifting
    cascade grows dynamic range (up to ~2 bits per level per step; more
    for weighted schemes like 97m), details go negative, and narrow
    predict sums wrap silently otherwise.  Mirrors
    ``lifting.promote_narrow`` so every backend matches the oracle."""
    if dtype in (jnp.int8, jnp.int16, jnp.int32, jnp.uint8, jnp.uint16):
        return jnp.dtype(jnp.int32)
    if dtype == jnp.int64:
        return jnp.dtype(jnp.int64)
    raise TypeError(f"integer DWT requires an int dtype, got {dtype}")


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Single-level kernel cores over 2D (rows, n) streams in the compute dtype.
# These are the bodies the fused multi-level path keeps resident.
# ---------------------------------------------------------------------------


def _pad_rows(a: jax.Array, rows_pad: int) -> jax.Array:
    rows = a.shape[0]
    if rows_pad == rows:
        return a
    return jnp.pad(a, ((0, rows_pad - rows), (0, 0)), mode="edge")


def _fwd_level(
    xf: jax.Array, scheme: str, mode: str, interpret: bool
) -> Tuple[jax.Array, jax.Array]:
    """One forward level over a 2D (rows, n) array; returns (s, d)."""
    sch = S.get_scheme(scheme)
    rows, n = xf.shape
    n_o = n // 2
    n_e = n - n_o
    if n_o < _MIN_KERNEL_PAIRS or not sch.can_window(n):
        return S.lift_fwd_axis(xf, scheme, axis=-1, mode=mode)

    halo = sch.halo
    block_rows, block_pairs = _backend.pick_blocks(rows, n_e)
    rows_pad = _ceil_to(rows, block_rows)
    pairs_pad = _ceil_to(n_e, block_pairs)
    n_tiles = pairs_pad // block_pairs
    wlen = 2 * block_pairs + 2 * halo
    # trace-time window maps: tile t covers core pairs [t*bp, (t+1)*bp),
    # i.e. samples [2*t*bp - halo, ...+wlen) reflected into range — every
    # window entry is an exact whole-point extension value.  Windows start
    # on an even sample (halo = 2*fwd_margin), so the stride-2 columns of
    # the map are the even / odd polyphase streams the kernel takes.
    idx = np.stack(
        [
            S.reflect_indices(2 * t * block_pairs - halo, wlen, n)
            for t in range(n_tiles)
        ]
    )
    xp = _pad_rows(xf, rows_pad)
    s, d = _k.lift_fwd_windows(
        _tile_major(xp, idx[:, 0::2]),
        _tile_major(xp, idx[:, 1::2]),
        scheme=sch,
        mode=mode,
        block_rows=block_rows,
        block_pairs=block_pairs,
        interpret=interpret,
    )
    return s[:rows, :n_e], d[:rows, :n_o]


def _tile_major(a: jax.Array, idx: np.ndarray) -> jax.Array:
    """(rows, n) gathered through (n_tiles, wlen) maps -> (n_tiles, rows, wlen)."""
    return jnp.transpose(a[:, idx], (1, 0, 2))


def _inv_level(
    sf: jax.Array, df: jax.Array, scheme: str, mode: str, interpret: bool
) -> jax.Array:
    """One inverse level over 2D (rows, n_e)/(rows, n_o) bands."""
    sch = S.get_scheme(scheme)
    rows, n_e = sf.shape
    n_o = df.shape[-1]
    n = n_e + n_o
    if n_o < _MIN_KERNEL_PAIRS or not sch.can_window(n):
        return S.lift_inv_axis(sf, df, scheme, axis=-1, mode=mode)

    m = sch.inv_margin
    block_rows, block_pairs = _backend.pick_blocks(rows, n_e)
    rows_pad = _ceil_to(rows, block_rows)
    pairs_pad = _ceil_to(n_e, block_pairs)
    n_tiles = pairs_pad // block_pairs
    wlen = block_pairs + 2 * m
    idx_s = np.stack(
        [
            S.reflect_entries(t * block_pairs - m, wlen, 0, n)
            for t in range(n_tiles)
        ]
    )
    idx_d = np.stack(
        [
            S.reflect_entries(t * block_pairs - m, wlen, 1, n)
            for t in range(n_tiles)
        ]
    )
    even, odd = _k.lift_inv_windows(
        _tile_major(_pad_rows(sf, rows_pad), idx_s),
        _tile_major(_pad_rows(df, rows_pad), idx_d),
        scheme=sch,
        mode=mode,
        block_rows=block_rows,
        block_pairs=block_pairs,
        interpret=interpret,
    )
    return S.merge_axis(even, odd, -1, 2 * pairs_pad)[:rows, :n]


# ---------------------------------------------------------------------------
# Jitted entry bodies (static backend decisions resolved by the wrappers).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scheme", "mode", "interpret"))
def _fwd_1d_kernel(x, scheme, mode, interpret):
    n = x.shape[-1]
    lead = x.shape[:-1]
    cdt = _compute_dtype(x.dtype)
    xf = x.reshape((-1, n)).astype(cdt)
    s, d = _fwd_level(xf, scheme, mode, interpret)
    return (
        s.reshape(lead + (s.shape[-1],)),
        d.reshape(lead + (d.shape[-1],)),
    )


@functools.partial(jax.jit, static_argnames=("scheme", "mode"))
def _fwd_1d_xla(x, scheme, mode):
    # delegate to the oracle (promotion included): one implementation of
    # the reference semantics keeps this path structurally bit-exact
    return _lift.dwt_fwd_1d(x, mode=mode, scheme=scheme)


@functools.partial(jax.jit, static_argnames=("scheme", "mode", "interpret"))
def _inv_1d_kernel(s, d, scheme, mode, interpret):
    n_e, n_o = s.shape[-1], d.shape[-1]
    lead = s.shape[:-1]
    cdt = _compute_dtype(s.dtype)
    sf = s.reshape((-1, n_e)).astype(cdt)
    df = d.reshape((-1, n_o)).astype(cdt)
    x = _inv_level(sf, df, scheme, mode, interpret)
    return x.reshape(lead + (n_e + n_o,))


@functools.partial(jax.jit, static_argnames=("scheme", "mode"))
def _inv_1d_xla(s, d, scheme, mode):
    return _lift.dwt_inv_1d(s, d, mode=mode, scheme=scheme)


@functools.partial(
    jax.jit, static_argnames=("levels", "scheme", "mode", "interpret")
)
def _fwd_multi_kernel(x, levels, scheme, mode, interpret):
    """Fused multi-level forward: one compiled computation for all levels.

    Flatten/promote once, keep the (rows, n) streams resident, recurse on
    the approximation in-graph — no per-level re-dispatch.
    """
    n = x.shape[-1]
    lead = x.shape[:-1]
    cdt = _compute_dtype(x.dtype)
    s = x.reshape((-1, n)).astype(cdt)
    details: List[jax.Array] = []
    for _ in range(levels):
        s, d = _fwd_level(s, scheme, mode, interpret)
        details.append(d)
    return (
        s.reshape(lead + (s.shape[-1],)),
        tuple(d.reshape(lead + (d.shape[-1],)) for d in reversed(details)),
    )


@functools.partial(jax.jit, static_argnames=("levels", "scheme", "mode"))
def _fwd_multi_xla(x, levels, scheme, mode):
    pyr = _lift.dwt_fwd(x, levels=levels, mode=mode, scheme=scheme)
    return pyr.approx, pyr.details


@functools.partial(jax.jit, static_argnames=("scheme", "mode", "interpret"))
def _inv_multi_kernel(approx, details, scheme, mode, interpret):
    """Fused multi-level inverse: all levels in one compiled computation."""
    lead = approx.shape[:-1]
    cdt = _compute_dtype(approx.dtype)
    s = approx.reshape((-1, approx.shape[-1])).astype(cdt)
    for d in details:  # coarsest first
        df = d.reshape((-1, d.shape[-1])).astype(cdt)
        s = _inv_level(s, df, scheme, mode, interpret)
    return s.reshape(lead + (s.shape[-1],))


@functools.partial(jax.jit, static_argnames=("scheme", "mode"))
def _inv_multi_xla(approx, details, scheme, mode):
    return _lift.dwt_inv(
        WaveletPyramid(approx=approx, details=tuple(details)),
        mode=mode,
        scheme=scheme,
    )


# ---------------------------------------------------------------------------
# Public API: backend-dispatched, bit-exact vs kernels/ref on every path.
# ---------------------------------------------------------------------------


def dwt_fwd_1d(
    x: jax.Array,
    mode: str = "paper",
    backend: Optional[str] = None,
    scheme="cdf53",
    checked=None,
) -> Tuple[jax.Array, jax.Array]:
    """Backend-dispatched forward transform along the last axis. N >= 2.

    Returns (s, d) with len(s) = ceil(N/2), len(d) = floor(N/2), matching
    ``core.lifting.dwt_fwd_1d`` bit-exactly for the same scheme.

    ``checked=True`` (or ``REPRO_DWT_CHECKED=1``) certifies the data
    against the derived range bounds first and raises
    :class:`~repro.resilience.errors.IntegerOverflowError` instead of
    ever returning wrapped bands (``core/ranges.py``) — same contract on
    every public transform in this package.
    """
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if x.shape[-1] < 2:
        raise ValueError("need at least 2 samples")
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked(
            lambda a: dwt_fwd_1d(a, mode=mode, backend=backend, scheme=sch,
                                 checked=False),
            x, scheme=sch, levels=1, mode=mode, ndim=1,
            label="kernels.dwt_fwd_1d",
        )
    b = _backend.resolve(backend)
    return _backend.pallas_guard(
        b, "dwt_fwd_1d", x,
        lambda: _fwd_1d_kernel(
            x, scheme=sch, mode=mode, interpret=_backend.interpret_flag(b)
        ),
        lambda: _fwd_1d_xla(x, scheme=sch, mode=mode),
    )


def dwt_inv_1d(
    s: jax.Array,
    d: jax.Array,
    mode: str = "paper",
    backend: Optional[str] = None,
    scheme="cdf53",
    checked=None,
) -> jax.Array:
    """Backend-dispatched inverse transform; bit-exact vs core.lifting."""
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if s.shape[-1] - d.shape[-1] not in (0, 1):
        raise ValueError("band length mismatch")
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked_inv(
            lambda t: dwt_inv_1d(t[0], t[1], mode=mode, backend=backend,
                                 scheme=sch, checked=False),
            (s, d), scheme=sch, levels=1, mode=mode, ndim=1,
            label="kernels.dwt_inv_1d",
        )
    b = _backend.resolve(backend)
    return _backend.pallas_guard(
        b, "dwt_inv_1d", (s, d),
        lambda: _inv_1d_kernel(
            s, d, scheme=sch, mode=mode, interpret=_backend.interpret_flag(b)
        ),
        lambda: _inv_1d_xla(s, d, scheme=sch, mode=mode),
    )


def dwt_fwd(
    x: jax.Array,
    levels: int = 1,
    mode: str = "paper",
    backend: Optional[str] = None,
    scheme="cdf53",
    checked=None,
) -> WaveletPyramid:
    """Fused multi-level forward transform (one compiled dispatch).

    ``levels=0`` is the identity pyramid, so ``levels=max_levels(n)``
    loops are safe on degenerate shapes.
    """
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if levels < 0:
        raise ValueError("levels must be >= 0")
    n = x.shape[-1]
    for _ in range(levels):
        if n < 2:
            raise ValueError(
                f"signal too short for {levels} levels (got {x.shape[-1]})"
            )
        n = n - n // 2
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked(
            lambda a: dwt_fwd(a, levels=levels, mode=mode, backend=backend,
                              scheme=sch, checked=False),
            x, scheme=sch, levels=levels, mode=mode, ndim=1,
            label="kernels.dwt_fwd",
        )
    b = _backend.resolve(backend)
    approx, details = _backend.pallas_guard(
        b, "dwt_fwd", x,
        lambda: _fwd_multi_kernel(
            x,
            levels=levels,
            scheme=sch,
            mode=mode,
            interpret=_backend.interpret_flag(b),
        ),
        lambda: _fwd_multi_xla(x, levels=levels, scheme=sch, mode=mode),
    )
    return WaveletPyramid(approx=approx, details=details)


def dwt_inv(
    pyr: WaveletPyramid,
    mode: str = "paper",
    backend: Optional[str] = None,
    scheme="cdf53",
    checked=None,
) -> jax.Array:
    """Fused multi-level inverse transform (one compiled dispatch)."""
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked_inv(
            lambda p: dwt_inv(p, mode=mode, backend=backend, scheme=sch,
                              checked=False),
            pyr, scheme=sch, levels=pyr.levels, mode=mode, ndim=1,
            label="kernels.dwt_inv",
        )
    # validate band lengths per level up front: every backend must reject a
    # malformed pyramid identically (the xla path raises inside ref, the
    # kernel path would otherwise silently reconstruct garbage)
    n = pyr.approx.shape[-1]
    for d in pyr.details:  # coarsest first
        if n - d.shape[-1] not in (0, 1):
            raise ValueError(
                f"band length mismatch: s={n}, d={d.shape[-1]}"
            )
        n = n + d.shape[-1]
    b = _backend.resolve(backend)
    return _backend.pallas_guard(
        b, "dwt_inv", pyr,
        lambda: _inv_multi_kernel(
            pyr.approx,
            tuple(pyr.details),
            scheme=sch,
            mode=mode,
            interpret=_backend.interpret_flag(b),
        ),
        lambda: _inv_multi_xla(
            pyr.approx, tuple(pyr.details), scheme=sch, mode=mode
        ),
    )


# ---------------------------------------------------------------------------
# (5,3) aliases — the seed's public names; nothing downstream breaks.
# ---------------------------------------------------------------------------


def dwt53_fwd_1d(
    x: jax.Array, mode: str = "paper", backend: Optional[str] = None
) -> Tuple[jax.Array, jax.Array]:
    return dwt_fwd_1d(x, mode=mode, backend=backend, scheme="cdf53")


def dwt53_inv_1d(
    s: jax.Array, d: jax.Array, mode: str = "paper", backend: Optional[str] = None
) -> jax.Array:
    return dwt_inv_1d(s, d, mode=mode, backend=backend, scheme="cdf53")


def dwt53_fwd(
    x: jax.Array,
    levels: int = 1,
    mode: str = "paper",
    backend: Optional[str] = None,
) -> WaveletPyramid:
    return dwt_fwd(x, levels=levels, mode=mode, backend=backend, scheme="cdf53")


def dwt53_inv(
    pyr: WaveletPyramid, mode: str = "paper", backend: Optional[str] = None
) -> jax.Array:
    return dwt_inv(pyr, mode=mode, backend=backend, scheme="cdf53")
