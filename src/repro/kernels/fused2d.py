"""Fused 2D (row-column) integer lifting DWT — a single tiled pass.

``core.lifting.dwt_fwd_2d`` composes the 1D transform with per-axis
stencils; a transpose-based layout would relayout the image through HBM
twice per level (and reshuffle across devices on a sharded axis).  This
module removes all of that:

  * The lifting cascade is applied ALONG AN AXIS (last for rows, -2 for
    columns) with pure slice/concat ops — ``schemes.lift_fwd_axis`` /
    ``lift_inv_axis``, the same band-policy math as the reference, for
    ANY registered scheme.
  * On the Pallas backends the whole row+column pipeline for one image
    tile runs inside ONE kernel: the grid iterates over the flattened
    batch, each cell loads its (H, W) image into VMEM once, computes the
    row lifting, feeds the resident s/d streams straight into the column
    lifting, and writes the four subbands (LL, LH, HL, HH) — one pass
    over HBM in, four band-writes out.  Images past the derived VMEM
    budget (``backend.fused2d_budget_elems``) stay on Pallas through the
    tiled halo-window engine (``kernels/tiled2d.py``), whose halo width
    is the scheme's — no XLA cliff.
  * On the XLA backend the same axis-aware math is one jitted program;
    XLA fuses both stages without materialising transposed copies.

This module is also the multi-level 2D dispatcher: ``dwt_fwd_2d_multi``
/ ``dwt_inv_2d_multi`` fuse the full Mallat pyramid into one compiled
dispatch on the Pallas engine, choosing whole-image or tiled kernels per
level from the static shapes.  Schemes that cannot run the windowed
tile dataflow on a given shape (``scheme.can_window``; e.g. cdf22's
antisymmetric lift, or haar on odd dims) use the whole-image kernel
within budget and in-graph band-policy math beyond it.

Bit-exactness: every path reproduces ``core.lifting.dwt_fwd_2d`` /
``dwt_inv_2d`` exactly, for every scheme, every (H, W) >= (2, 2)
including odd sizes and both rounding modes; tests sweep this.  See
DESIGN.md §5-6 and §9.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import lifting as _lift
from repro.core import ranges as _ranges
from repro.core import schemes as S
from repro.core.lifting import (
    Bands2D,
    Pyramid2D,
    _check_mode,
    check_levels_2d,
)
from repro.kernels import backend as _backend
from repro.kernels import tiled2d as _tiled
from repro.kernels.ops import _compute_dtype

Array = jax.Array


def _fwd2d_math(x: Array, mode: str, scheme="cdf53"):
    """One reference 2D level (``core.lifting.dwt_fwd_2d``) as a tuple.

    Delegating to the oracle — not re-composing the axis cascade here —
    keeps the 'xla backend == oracle' contract structural: there is one
    implementation of the level composition to maintain.
    """
    b = _lift.dwt_fwd_2d(x, mode=mode, scheme=scheme)
    return b.ll, b.lh, b.hl, b.hh


def _inv2d_math(
    ll: Array, lh: Array, hl: Array, hh: Array, mode: str, scheme="cdf53"
) -> Array:
    return _lift.dwt_inv_2d(
        Bands2D(ll=ll, lh=lh, hl=hl, hh=hh), mode=mode, scheme=scheme
    )


# ---------------------------------------------------------------------------
# Pallas fused kernel: one grid cell = one image, rows+columns in VMEM.
# The kernel body runs the band-policy reference cascade on the image's
# polyphase components (split / merged by XLA around the call: Mosaic
# cannot lay out an in-kernel lane split), so the whole-image path
# supports every registered scheme (windowability not required).  Refs
# are in band-code order: ll, hl, lh, hh.
# ---------------------------------------------------------------------------


def _fwd2d_kernel(*refs, scheme, mode: str, h: int, w: int):
    comp_refs, band_refs = refs[:4], refs[4:]
    bands = S.lift_fwd_split([r[0] for r in comp_refs], scheme, mode, (w, h))
    for ref, b in zip(band_refs, bands):
        ref[0] = b


def _inv2d_kernel(*refs, scheme, mode: str, h: int, w: int):
    band_refs, comp_refs = refs[:4], refs[4:]
    comps = S.lift_inv_split([r[0] for r in band_refs], scheme, mode, (w, h))
    for ref, c in zip(comp_refs, comps):
        ref[0] = c


def _img_spec(shape):
    return pl.BlockSpec((1,) + tuple(shape), lambda b: (b, 0, 0))


@functools.partial(jax.jit, static_argnames=("scheme", "mode", "interpret"))
def _fwd2d_pallas(x: Array, scheme: str, mode: str, interpret: bool):
    h, w = x.shape[-2:]
    comps = S.polyphase_split(x, 2)  # forward bands share the comp shapes
    ll, hl, lh, hh = pl.pallas_call(
        functools.partial(_fwd2d_kernel, scheme=scheme, mode=mode, h=h, w=w),
        grid=(x.shape[0],),
        in_specs=[_img_spec(c.shape[1:]) for c in comps],
        out_specs=tuple(_img_spec(c.shape[1:]) for c in comps),
        out_shape=tuple(jax.ShapeDtypeStruct(c.shape, x.dtype) for c in comps),
        interpret=interpret,
    )(*comps)
    return ll, lh, hl, hh


@functools.partial(jax.jit, static_argnames=("scheme", "mode", "interpret"))
def _inv2d_pallas(
    ll: Array, lh: Array, hl: Array, hh: Array,
    scheme: str, mode: str, interpret: bool,
):
    h = ll.shape[1] + lh.shape[1]
    w = ll.shape[2] + hl.shape[2]
    bands = (ll, hl, lh, hh)
    comps = pl.pallas_call(
        functools.partial(_inv2d_kernel, scheme=scheme, mode=mode, h=h, w=w),
        grid=(ll.shape[0],),
        in_specs=[_img_spec(b.shape[1:]) for b in bands],
        out_specs=tuple(_img_spec(b.shape[1:]) for b in bands),
        out_shape=tuple(jax.ShapeDtypeStruct(b.shape, ll.dtype) for b in bands),
        interpret=interpret,
    )(*bands)
    return S.polyphase_merge(list(comps), (h, w))


@functools.partial(jax.jit, static_argnames=("scheme", "mode"))
def _fwd2d_xla(x: Array, scheme: str, mode: str):
    return _fwd2d_math(x.astype(_compute_dtype(x.dtype)), mode, scheme)


@functools.partial(jax.jit, static_argnames=("scheme", "mode"))
def _inv2d_xla(ll: Array, lh: Array, hl: Array, hh: Array, scheme: str, mode: str):
    cdt = _compute_dtype(ll.dtype)
    return _inv2d_math(
        ll.astype(cdt), lh.astype(cdt), hl.astype(cdt), hh.astype(cdt),
        mode, scheme,
    )


# ---------------------------------------------------------------------------
# Level dispatch: whole-image kernel within the VMEM budget, tiled
# halo-window kernel beyond it (kernels/tiled2d.py) — Pallas either way.
# ---------------------------------------------------------------------------


def _fits_vmem(h: int, w: int) -> bool:
    return h * w <= _backend.fused2d_budget_elems()


def _can_tile(h: int, w: int, scheme: str) -> bool:
    # the tiled engine's window dataflow must reproduce the band policy
    # along both dims (scheme.can_window: symmetric schemes anywhere,
    # halo-0 schemes on even dims, never cdf22-style antisymmetric lifts)
    sch = S.get_scheme(scheme)
    return sch.can_window(h) and sch.can_window(w)


def _use_tiled(h: int, w: int, scheme: str = "cdf53") -> bool:
    return _can_tile(h, w, scheme) and (
        _backend.tile_forced() or not _fits_vmem(h, w)
    )


def _fwd2d_level(x3: Array, scheme: str, mode: str, interpret: bool):
    """One forward level on a (B, H, W) compute-dtype batch (trace-time
    whole-image/tiled choice; both are Pallas)."""
    h, w = x3.shape[-2], x3.shape[-1]
    if _use_tiled(h, w, scheme):
        th, tw = _backend.pick_tile(h, w, S.get_scheme(scheme).halo)
        return _tiled.fwd2d_tiled(x3, mode, th, tw, interpret, scheme=scheme)
    if _fits_vmem(h, w):
        return _fwd2d_pallas(x3, scheme=scheme, mode=mode, interpret=interpret)
    # over budget but untileable (a dim < 3 / an unwindowable scheme):
    # in-graph jnp math — never an image-sized VMEM block.  Level 0
    # additionally warns via _resolve_2d.
    return _fwd2d_math(x3, mode, scheme)


def _inv2d_level(ll3, lh3, hl3, hh3, scheme: str, mode: str, interpret: bool):
    h = ll3.shape[-2] + lh3.shape[-2]
    w = ll3.shape[-1] + hl3.shape[-1]
    if _use_tiled(h, w, scheme):
        th, tw = _backend.pick_tile(h, w, S.get_scheme(scheme).halo)
        return _tiled.inv2d_tiled(
            ll3, lh3, hl3, hh3, mode, th, tw, interpret, scheme=scheme
        )
    if _fits_vmem(h, w):
        return _inv2d_pallas(
            ll3, lh3, hl3, hh3, scheme=scheme, mode=mode, interpret=interpret
        )
    return _inv2d_math(ll3, lh3, hl3, hh3, mode, scheme)  # see _fwd2d_level


def _resolve_2d(backend: Optional[str], h: int, w: int, scheme: str = "cdf53") -> str:
    """Backend for a 2D transform; names the one remaining budget cliff.

    Images too degenerate (or schemes too asymmetric) to tile that also
    exceed the whole-image budget cannot run under Pallas; they degrade
    to the (unbounded, bit-exact) XLA path with a one-time warning.
    """
    b = _backend.resolve(backend)
    if b != "xla" and not _fits_vmem(h, w) and not _can_tile(h, w, scheme):
        _backend.note_degrade(
            b, "xla",
            f"budget: ({h}, {w}) exceeds the whole-image VMEM budget and "
            f"scheme {S.get_scheme(scheme).name!r} cannot take the tiled "
            "halo path there",
        )
        return "xla"
    return b


def plan_2d(
    h: int, w: int, backend: Optional[str] = None, scheme: str = "cdf53"
) -> str:
    """Name the execution path a (h, w) 2D transform will take.

    One of ``whole-pallas`` / ``tiled-pallas`` / ``whole-interpret`` /
    ``tiled-interpret`` / ``xla``.  Benchmarks and the CI smoke gate use
    this to assert that budget-sized images never silently leave the
    Pallas path on an accelerator.
    """
    sch = S.get_scheme(scheme)
    b = _resolve_2d(backend, h, w, sch)
    if b == "xla":
        return "xla"
    kind = "tiled" if _use_tiled(h, w, sch) else "whole"
    return f"{kind}-{'interpret' if b == 'interpret' else 'pallas'}"


# ---------------------------------------------------------------------------
# Public API.
# ---------------------------------------------------------------------------


def dwt_fwd_2d(
    x: Array, mode: str = "paper", backend: Optional[str] = None,
    scheme="cdf53", checked=None,
) -> Bands2D:
    """One fused 2D level over the last two axes (rows then columns).

    Runs the whole-image Pallas kernel when the image fits the VMEM
    budget and the tiled halo-window kernel when it does not — there is
    no large-image XLA cliff.  Bit-exact vs ``core.lifting.dwt_fwd_2d``
    on every backend, for every registered scheme.  ``checked=True`` (or
    ``REPRO_DWT_CHECKED=1``) certifies the data against the derived
    range bounds and raises ``IntegerOverflowError`` instead of ever
    returning wrapped bands (``core/ranges.py``).
    """
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if x.ndim < 2 or x.shape[-1] < 2 or x.shape[-2] < 2:
        raise ValueError(f"need a (..., H>=2, W>=2) input, got {x.shape}")
    h, w = x.shape[-2], x.shape[-1]
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked(
            lambda a: dwt_fwd_2d(a, mode=mode, backend=backend, scheme=sch,
                                 checked=False),
            x, scheme=sch, levels=1, mode=mode, ndim=2,
            label="kernels.dwt_fwd_2d",
        )
    b = _resolve_2d(backend, h, w, sch)

    def _kernel() -> Bands2D:
        lead = x.shape[:-2]
        xf = x.reshape((-1, h, w)).astype(_compute_dtype(x.dtype))
        ll, lh, hl, hh = _fwd2d_level(xf, sch, mode, _backend.interpret_flag(b))
        return Bands2D(
            ll=ll.reshape(lead + ll.shape[1:]),
            lh=lh.reshape(lead + lh.shape[1:]),
            hl=hl.reshape(lead + hl.shape[1:]),
            hh=hh.reshape(lead + hh.shape[1:]),
        )

    def _xla() -> Bands2D:
        ll, lh, hl, hh = _fwd2d_xla(x, scheme=sch, mode=mode)
        return Bands2D(ll=ll, lh=lh, hl=hl, hh=hh)

    return _backend.pallas_guard(b, "dwt_fwd_2d", x, _kernel, _xla)


def dwt_inv_2d(
    bands: Bands2D, mode: str = "paper", backend: Optional[str] = None,
    scheme="cdf53", checked=None,
) -> Array:
    """Fused inverse of :func:`dwt_fwd_2d` (columns then rows)."""
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked_inv(
            lambda b_: dwt_inv_2d(b_, mode=mode, backend=backend, scheme=sch,
                                  checked=False),
            bands, scheme=sch, levels=1, mode=mode, ndim=2,
            label="kernels.dwt_inv_2d",
        )
    ll = bands.ll
    h = ll.shape[-2] + bands.lh.shape[-2]
    w = ll.shape[-1] + bands.hl.shape[-1]
    b = _resolve_2d(backend, h, w, sch)

    def _kernel() -> Array:
        lead = ll.shape[:-2]
        cdt = _compute_dtype(ll.dtype)
        args = tuple(
            a.reshape((-1,) + a.shape[len(lead) :]).astype(cdt)
            for a in (bands.ll, bands.lh, bands.hl, bands.hh)
        )
        x = _inv2d_level(
            *args, scheme=sch, mode=mode, interpret=_backend.interpret_flag(b)
        )
        return x.reshape(lead + x.shape[1:])

    return _backend.pallas_guard(
        b, "dwt_inv_2d", bands, _kernel,
        lambda: _inv2d_xla(
            bands.ll, bands.lh, bands.hl, bands.hh, scheme=sch, mode=mode
        ),
    )


# ---------------------------------------------------------------------------
# Fused multi-level 2D Mallat pyramid: one compiled dispatch for every
# level (mirrors the 1D fusion in kernels/ops.py).  The per-level
# whole-image/tiled choice is made at trace time from the static shapes,
# so a 2048x2048 pyramid runs tiled at the fine levels and whole-image at
# the coarse ones — all inside one executable.
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("levels", "scheme", "mode", "interpret", "dispatch")
)
def _fwd2d_multi_kernel(x, levels, scheme, mode, interpret, dispatch):
    # `dispatch` (backend.dispatch_state()) keys the jit cache on the env
    # overrides so REPRO_DWT_TILE / REPRO_DWT_VMEM_MB retrace, not no-op
    ll = x.astype(_compute_dtype(x.dtype))  # in-jit: no eager host copy
    details: List[Tuple[Array, Array, Array]] = []
    for _ in range(levels):
        ll, lh, hl, hh = _fwd2d_level(ll, scheme, mode, interpret)
        details.append((lh, hl, hh))
    return ll, tuple(reversed(details))


def _fwd2d_multi_xla(x, levels, scheme, mode):
    # per-level jitted dispatches, NOT one fused program: XLA:CPU compiles
    # the chained multi-level graph ~2x slower (it refuses to materialise
    # level l's bands cleanly for level l+1 even behind an
    # optimization_barrier — measured in BENCH_kernels.json history).  The
    # single-dispatch fusion is a property of the Pallas engine, whose
    # per-level kernels are opaque custom calls XLA cannot mis-fuse.
    ll = x
    details: List[Tuple[Array, Array, Array]] = []
    for _ in range(levels):
        ll, lh, hl, hh = _fwd2d_xla(ll, scheme=scheme, mode=mode)
        details.append((lh, hl, hh))
    return ll, tuple(reversed(details))


@functools.partial(
    jax.jit, static_argnames=("scheme", "mode", "interpret", "dispatch")
)
def _inv2d_multi_kernel(ll, details, scheme, mode, interpret, dispatch):
    cdt = _compute_dtype(ll.dtype)  # in-jit promotion: no eager copies
    ll = ll.astype(cdt)
    for lh, hl, hh in details:  # coarsest first
        ll = _inv2d_level(
            ll, lh.astype(cdt), hl.astype(cdt), hh.astype(cdt),
            scheme, mode, interpret,
        )
    return ll


def _inv2d_multi_xla(ll, details, scheme, mode):
    for lh, hl, hh in details:  # per-level dispatch: see _fwd2d_multi_xla
        ll = _inv2d_xla(ll, lh, hl, hh, scheme=scheme, mode=mode)
    return ll


def dwt_fwd_2d_multi(
    x: Array,
    levels: int = 1,
    mode: str = "paper",
    backend: Optional[str] = None,
    scheme="cdf53",
    checked=None,
) -> Pyramid2D:
    """Fused multi-level 2D forward transform.

    On the Pallas engine (accelerator default) every level traces into
    ONE compiled dispatch — fine levels tiled, coarse levels whole-image.
    The XLA reference path dispatches per level (faster there: see
    ``_fwd2d_multi_xla``).
    """
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if x.ndim < 2:
        raise ValueError(f"need a (..., H, W) input, got {x.shape}")
    h, w = x.shape[-2], x.shape[-1]
    check_levels_2d(h, w, levels)
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked(
            lambda a: dwt_fwd_2d_multi(a, levels=levels, mode=mode,
                                       backend=backend, scheme=sch,
                                       checked=False),
            x, scheme=sch, levels=levels, mode=mode, ndim=2,
            label="kernels.dwt_fwd_2d_multi",
        )
    b = _resolve_2d(backend, h, w, sch)
    lead = x.shape[:-2]

    def _kernel() -> Pyramid2D:
        xf = x.reshape((-1, h, w))  # metadata-only; promotion happens in-jit
        ll, details = _fwd2d_multi_kernel(
            xf, levels=levels, scheme=sch, mode=mode,
            interpret=_backend.interpret_flag(b),
            dispatch=_backend.dispatch_state(),
        )

        def unlead(a: Array) -> Array:
            return a.reshape(lead + a.shape[1:])

        return Pyramid2D(
            ll=unlead(ll),
            details=tuple(
                (unlead(lh), unlead(hl), unlead(hh)) for lh, hl, hh in details
            ),
        )

    def _xla() -> Pyramid2D:
        # _fwd2d_xla promotes in-jit; no eager cast of the full image here
        ll, details = _fwd2d_multi_xla(x, levels=levels, scheme=sch, mode=mode)
        return Pyramid2D(ll=ll, details=details)

    return _backend.pallas_guard(b, "dwt_fwd_2d_multi", x, _kernel, _xla)


def dwt_inv_2d_multi(
    pyr: Pyramid2D, mode: str = "paper", backend: Optional[str] = None,
    scheme="cdf53", checked=None,
) -> Array:
    """Inverse of :func:`dwt_fwd_2d_multi` (one dispatch on Pallas)."""
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked_inv(
            lambda p: dwt_inv_2d_multi(p, mode=mode, backend=backend,
                                       scheme=sch, checked=False),
            pyr, scheme=sch, levels=len(pyr.details), mode=mode, ndim=2,
            label="kernels.dwt_inv_2d_multi",
        )
    ll = pyr.ll
    h, w = ll.shape[-2], ll.shape[-1]
    for lh, hl, hh in pyr.details:  # validate band geometry coarsest-first
        if (
            lh.shape[-2] not in (h, h - 1)
            or hl.shape[-1] not in (w, w - 1)
            or hl.shape[-2] != h
            or lh.shape[-1] != w
            or hh.shape[-2:] != (lh.shape[-2], hl.shape[-1])
        ):
            raise ValueError(
                f"band shape mismatch at ll={(h, w)}: "
                f"lh={lh.shape[-2:]}, hl={hl.shape[-2:]}, hh={hh.shape[-2:]}"
            )
        h, w = h + lh.shape[-2], w + hl.shape[-1]
    b = _resolve_2d(backend, h, w, sch)

    def _kernel() -> Array:
        lead = ll.shape[:-2]

        def flat(a: Array) -> Array:
            return a.reshape((-1,) + a.shape[len(lead) :])  # metadata-only

        details = tuple(
            (flat(lh), flat(hl), flat(hh)) for lh, hl, hh in pyr.details
        )
        x = _inv2d_multi_kernel(
            flat(ll), details, scheme=sch, mode=mode,
            interpret=_backend.interpret_flag(b),
            dispatch=_backend.dispatch_state(),
        )
        return x.reshape(lead + x.shape[1:])

    return _backend.pallas_guard(
        b, "dwt_inv_2d_multi", pyr, _kernel,
        # _inv2d_xla promotes in-jit; pass the bands through untouched
        lambda: _inv2d_multi_xla(ll, tuple(pyr.details), scheme=sch, mode=mode),
    )


# ---------------------------------------------------------------------------
# (5,3) aliases — the seed's public names; nothing downstream breaks.
# ---------------------------------------------------------------------------


def dwt53_fwd_2d(
    x: Array, mode: str = "paper", backend: Optional[str] = None
) -> Bands2D:
    return dwt_fwd_2d(x, mode=mode, backend=backend, scheme="cdf53")


def dwt53_inv_2d(
    bands: Bands2D, mode: str = "paper", backend: Optional[str] = None
) -> Array:
    return dwt_inv_2d(bands, mode=mode, backend=backend, scheme="cdf53")


def dwt53_fwd_2d_multi(
    x: Array,
    levels: int = 1,
    mode: str = "paper",
    backend: Optional[str] = None,
) -> Pyramid2D:
    return dwt_fwd_2d_multi(x, levels=levels, mode=mode, backend=backend, scheme="cdf53")


def dwt53_inv_2d_multi(
    pyr: Pyramid2D, mode: str = "paper", backend: Optional[str] = None
) -> Array:
    return dwt_inv_2d_multi(pyr, mode=mode, backend=backend, scheme="cdf53")
