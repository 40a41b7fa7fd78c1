"""Fused N-D integer lifting DWT with a first-class 3-D volume engine.

The lifting steps are dimension-agnostic — the same multiplierless
shift-add predict/update pairs compose separably along any axis (the 3-D
separable structure of "High Speed VLSI Architecture for 3-D Discrete
Wavelet Transform" maps onto the same parallel module layout as the
paper's 1-D/2-D modules) — so this module generalizes the transform
stack past the hardcoded 1D/2D entry points:

  * ``dwt_fwd_nd`` / ``dwt_inv_nd`` — the public N-D API
    (``repro.kernels``): ndim=1/2 route through the existing fused
    engines (``kernels/ops.py`` / ``kernels/fused2d.py``) and are
    re-wrapped as :class:`PyramidND`; ndim=3 runs the fused volume
    engine below; ndim>3 runs the per-level jitted reference.
  * Whole-volume Pallas kernel: one grid cell per volume, the full
    row/column/depth cascade on the resident (D, H, W) block — one pass
    over HBM in, eight octant-band writes out.  The kernel body IS the
    band-policy reference math, so every registered scheme is supported
    (windowability not required).
  * Slab-tiled kernel for volumes past the derived VMEM budget
    (``backend.fused3d_budget_elems``): the volume is blocked along the
    DEPTH axis only — slabs of TD slices extended by the scheme's
    reflect halo (``scheme.halo``, mirroring ``kernels/tiled2d.py``'s
    windows), H and W fully resident per slab.  The plane axes run the
    exact band-policy math per depth slice (any scheme), and the slab
    axis runs the interior window math
    (``schemes.lift_{fwd,inv}_axis_ext``), so only the DEPTH axis needs
    ``scheme.can_window``.  Correctness rests on the tiled2d identity:
    for reflection-commuting schemes the reference's whole boundary
    policy IS whole-point reflect extension of the input, and per-slice
    plane transforms commute with depth reflection trivially.
  * Volumes that neither fit the budget nor can slab (degenerate planes
    bigger than the budget, unwindowable depth) degrade to the
    unbounded, bit-exact XLA path with a one-time
    ``BackendDegradeWarning`` — never a silent cliff.

Multi-level: ``dwt_fwd_nd``/``dwt_inv_nd`` fuse the full N-D Mallat
pyramid into one compiled dispatch on the Pallas engine (per-level
whole-volume/slab choice at trace time from static shapes), per-level
jitted dispatches on XLA:CPU (same rationale as ``fused2d``).

Bit-exactness: every path reproduces ``core.lifting.dwt_fwd_nd`` /
``dwt_inv_nd`` exactly, for every registered scheme, every mode, and
every shape with all transform axes >= 2 (odd sizes included); tests
sweep this.  See DESIGN.md §10.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import pallas as pl

from repro.core import lifting as _lift
from repro.core import ranges as _ranges
from repro.core import schemes as S
from repro.core.lifting import PyramidND, _check_mode, check_levels_nd
from repro.kernels import backend as _backend
from repro.kernels import fused2d as _f2d
from repro.kernels import ops as _ops
from repro.kernels.ops import _compute_dtype

Array = jax.Array

_N_BANDS_3D = 8  # 2**3 band octants per level, code order (bit j = axis -(j+1))


def _fwd3d_math(x: Array, mode: str, scheme) -> List[Array]:
    """One reference 3D level as the code-ordered band list (oracle math)."""
    return _lift._fwd_nd_level(x, 3, mode, scheme)


def _inv3d_math(bands: Sequence[Array], mode: str, scheme) -> Array:
    return _lift._inv_nd_level(list(bands), 3, mode, scheme)


def _band_dims_3d(d: int, h: int, w: int) -> List[Tuple[int, int, int]]:
    """Per-code (depth, height, width) band shapes for one 3D level."""
    ev = (d - d // 2, h - h // 2, w - w // 2)
    od = (d // 2, h // 2, w // 2)
    out = []
    for code in range(_N_BANDS_3D):
        out.append(
            (
                od[0] if code & 4 else ev[0],  # bit 2: axis -3 (depth)
                od[1] if code & 2 else ev[1],  # bit 1: axis -2
                od[2] if code & 1 else ev[2],  # bit 0: axis -1
            )
        )
    return out


# ---------------------------------------------------------------------------
# Whole-volume Pallas kernel: one grid cell = one (D, H, W) volume.  Like
# the 2D kernels it runs on the polyphase components (code order, split
# and merged by XLA around the call), so no in-kernel lane split.
# ---------------------------------------------------------------------------


def _fwd3d_kernel(*refs, scheme, mode: str, dhw):
    comp_refs, band_refs = refs[:_N_BANDS_3D], refs[_N_BANDS_3D:]
    bands = S.lift_fwd_split(
        [r[0] for r in comp_refs], scheme, mode, tuple(reversed(dhw))
    )
    for ref, b in zip(band_refs, bands):
        ref[0] = b


def _inv3d_kernel(*refs, scheme, mode: str, dhw):
    band_refs, comp_refs = refs[:_N_BANDS_3D], refs[_N_BANDS_3D:]
    comps = S.lift_inv_split(
        [r[0] for r in band_refs], scheme, mode, tuple(reversed(dhw))
    )
    for ref, c in zip(comp_refs, comps):
        ref[0] = c


def _vol_spec(d: int, h: int, w: int):
    return pl.BlockSpec((1, d, h, w), lambda b: (b, 0, 0, 0))


@functools.partial(jax.jit, static_argnames=("scheme", "mode", "interpret"))
def _fwd3d_pallas(x: Array, scheme, mode: str, interpret: bool):
    bsz, d, h, w = x.shape
    dims = _band_dims_3d(d, h, w)  # == the polyphase component shapes
    return pl.pallas_call(
        functools.partial(_fwd3d_kernel, scheme=scheme, mode=mode, dhw=(d, h, w)),
        grid=(bsz,),
        in_specs=[_vol_spec(*dim) for dim in dims],
        out_specs=tuple(_vol_spec(*dim) for dim in dims),
        out_shape=tuple(
            jax.ShapeDtypeStruct((bsz,) + dim, x.dtype) for dim in dims
        ),
        interpret=interpret,
    )(*S.polyphase_split(x, 3))


@functools.partial(jax.jit, static_argnames=("scheme", "mode", "interpret"))
def _inv3d_pallas(bands: Tuple[Array, ...], scheme, mode: str, interpret: bool):
    bsz = bands[0].shape[0]
    d = bands[0].shape[1] + bands[4].shape[1]
    h = bands[0].shape[2] + bands[2].shape[2]
    w = bands[0].shape[3] + bands[1].shape[3]
    dims = _band_dims_3d(d, h, w)
    comps = pl.pallas_call(
        functools.partial(_inv3d_kernel, scheme=scheme, mode=mode, dhw=(d, h, w)),
        grid=(bsz,),
        in_specs=[_vol_spec(*dim) for dim in dims],
        out_specs=tuple(_vol_spec(*dim) for dim in dims),
        out_shape=tuple(
            jax.ShapeDtypeStruct((bsz,) + dim, bands[0].dtype) for dim in dims
        ),
        interpret=interpret,
    )(*bands)
    return S.polyphase_merge(list(comps), (d, h, w))


# ---------------------------------------------------------------------------
# Slab-tiled Pallas kernel: depth-blocked halo windows, planes resident.
# The plane axes run the exact band-policy cascade per depth slice (the
# reference's own composition order: -1 then -2); the slab axis runs
# interior window math on the reflect-extended depth streams.
# ---------------------------------------------------------------------------


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _fwd_slab_kernel(*refs, scheme, mode: str, hw):
    win_refs, band_refs = refs[:_N_BANDS_3D], refs[_N_BANDS_3D:]
    bands = S.lift_fwd_split(
        [r[0, 0] for r in win_refs], scheme, mode, (hw[1], hw[0], None)
    )
    for ref, b in zip(band_refs, bands):
        ref[0] = b


def _inv_slab_kernel(*refs, scheme, mode: str, hw):
    band_refs, comp_refs = refs[:_N_BANDS_3D], refs[_N_BANDS_3D:]
    comps = S.lift_inv_split(
        [r[0, 0] for r in band_refs], scheme, mode, (hw[1], hw[0], None)
    )
    for ref, c in zip(comp_refs, comps):
        ref[0] = c


def _slab_win_spec(wd: int, h: int, w: int):
    """One (1,1,wd,h,w) depth window per (b, i) grid cell."""
    return pl.BlockSpec((1, 1, wd, h, w), lambda b, i: (b, i, 0, 0, 0))


def _slab_out_spec(bd: int, h: int, w: int):
    """A (1,bd,h,w) depth block of a (B, n*bd, h, w) output per cell."""
    return pl.BlockSpec((1, bd, h, w), lambda b, i: (b, i, 0, 0))


def _depth_windows(x: Array, rows: np.ndarray) -> Array:
    """(B, D', H, W) -> (B, n_slabs, wd, H, W) overlapping depth windows."""
    return x[:, rows]


@functools.partial(
    jax.jit, static_argnames=("scheme", "mode", "td", "interpret")
)
def fwd3d_slab(
    x: Array, mode: str, td: int, interpret: bool, scheme="cdf53"
):
    """Slab-tiled forward 3D level over a (B, D, H, W) batch.

    Returns the 8 code-ordered bands with the reference shapes.
    Bit-exact vs ``core.lifting.dwt_fwd_nd`` for every scheme/shape the
    dispatcher routes here (``scheme.can_window(D)``).
    """
    sch = S.get_scheme(scheme)
    halo = sch.halo
    m = sch.fwd_margin
    bsz, d, h, w = x.shape
    dims = _band_dims_3d(d, h, w)
    d_e = d - d // 2
    bd = td // 2
    n_slabs = _ceil_to(d_e, bd) // bd
    rows = np.stack(
        [
            S.reflect_indices(t * td - halo, td + 2 * halo, d)
            for t in range(n_slabs)
        ]
    )
    # windows start on an even slice (halo = 2*fwd_margin): the stride-2
    # rows of the map are the depth-even / depth-odd slices
    planes = S.polyphase_split(x, 2)
    wins = [
        _depth_windows(planes[code & 3], rows[:, (code >> 2) & 1 :: 2])
        for code in range(_N_BANDS_3D)
    ]
    bands = pl.pallas_call(
        functools.partial(_fwd_slab_kernel, scheme=sch, mode=mode, hw=(h, w)),
        grid=(bsz, n_slabs),
        in_specs=[
            _slab_win_spec(bd + 2 * m, *dim[1:]) for dim in dims
        ],
        out_specs=tuple(_slab_out_spec(bd, *dim[1:]) for dim in dims),
        out_shape=tuple(
            jax.ShapeDtypeStruct((bsz, n_slabs * bd) + dim[1:], x.dtype)
            for dim in dims
        ),
        interpret=interpret,
    )(*wins)
    return tuple(b[:, : dim[0]] for b, dim in zip(bands, dims))


@functools.partial(
    jax.jit, static_argnames=("scheme", "mode", "td", "interpret")
)
def inv3d_slab(
    bands: Tuple[Array, ...], mode: str, td: int, interpret: bool,
    scheme="cdf53",
):
    """Slab-tiled inverse of :func:`fwd3d_slab`."""
    sch = S.get_scheme(scheme)
    m = sch.inv_margin
    bsz = bands[0].shape[0]
    d = bands[0].shape[1] + bands[4].shape[1]
    h = bands[0].shape[2] + bands[2].shape[2]
    w = bands[0].shape[3] + bands[1].shape[3]
    d_e = d - d // 2
    me = td // 2
    n_slabs = _ceil_to(d_e, me) // me
    # band-entry depth windows per polyphase role: codes 0-3 are the
    # depth-even (s) stream, codes 4-7 the depth-odd (d) stream; every
    # window entry is an exact policy extension (schemes.reflect_entries)
    idx = {
        parity: np.stack(
            [
                S.reflect_entries(t * me - m, me + 2 * m, parity, d)
                for t in range(n_slabs)
            ]
        )
        for parity in (0, 1)
    }
    wins = tuple(
        _depth_windows(b, idx[(code >> 2) & 1])
        for code, b in enumerate(bands)
    )
    dims = _band_dims_3d(d, h, w)
    comps = pl.pallas_call(
        functools.partial(_inv_slab_kernel, scheme=sch, mode=mode, hw=(h, w)),
        grid=(bsz, n_slabs),
        in_specs=[
            _slab_win_spec(me + 2 * m, *dims[code][1:])
            for code in range(_N_BANDS_3D)
        ],
        out_specs=tuple(_slab_out_spec(me, *dim[1:]) for dim in dims),
        out_shape=tuple(
            jax.ShapeDtypeStruct((bsz, n_slabs * me) + dim[1:], bands[0].dtype)
            for dim in dims
        ),
        interpret=interpret,
    )(*wins)
    x = S.polyphase_merge(list(comps), (n_slabs * td, h, w))
    return x[:, :d]


# ---------------------------------------------------------------------------
# Level dispatch + the XLA reference path.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scheme", "mode"))
def _fwd3d_xla(x: Array, scheme, mode: str):
    return tuple(_fwd3d_math(x.astype(_compute_dtype(x.dtype)), mode, scheme))


@functools.partial(jax.jit, static_argnames=("scheme", "mode"))
def _inv3d_xla(bands: Tuple[Array, ...], scheme, mode: str):
    cdt = _compute_dtype(bands[0].dtype)
    return _inv3d_math([b.astype(cdt) for b in bands], mode, scheme)


def _fits_vmem3(d: int, h: int, w: int) -> bool:
    return d * h * w <= _backend.fused3d_budget_elems()


def _can_slab(d: int, h: int, w: int, scheme) -> bool:
    # only the slab (depth) axis needs the windowed dataflow; the plane
    # axes run exact band-policy math inside the kernel, so any scheme
    # works along H/W — but the slab windows themselves must fit VMEM
    sch = S.get_scheme(scheme)
    return sch.can_window(d) and _backend.slab_fits(h, w, sch.halo)


def _use_slab(d: int, h: int, w: int, scheme) -> bool:
    return _can_slab(d, h, w, scheme) and (
        _backend.slab_forced() or not _fits_vmem3(d, h, w)
    )


def _fwd3d_level(x4: Array, scheme, mode: str, interpret: bool):
    """One forward level on a (B, D, H, W) compute-dtype batch
    (trace-time whole-volume/slab choice; both are Pallas)."""
    d, h, w = x4.shape[-3:]
    if _use_slab(d, h, w, scheme):
        td = _backend.pick_slab(d, h, w, S.get_scheme(scheme).halo)
        return fwd3d_slab(x4, mode, td, interpret, scheme=scheme)
    if _fits_vmem3(d, h, w):
        return _fwd3d_pallas(x4, scheme=scheme, mode=mode, interpret=interpret)
    # over budget but un-slab-able: in-graph jnp math — never a
    # volume-sized VMEM block.  Level 0 additionally warns via _resolve_3d.
    return tuple(_fwd3d_math(x4, mode, scheme))


def _inv3d_level(bands, scheme, mode: str, interpret: bool):
    d = bands[0].shape[-3] + bands[4].shape[-3]
    h = bands[0].shape[-2] + bands[2].shape[-2]
    w = bands[0].shape[-1] + bands[1].shape[-1]
    if _use_slab(d, h, w, scheme):
        td = _backend.pick_slab(d, h, w, S.get_scheme(scheme).halo)
        return inv3d_slab(tuple(bands), mode, td, interpret, scheme=scheme)
    if _fits_vmem3(d, h, w):
        return _inv3d_pallas(
            tuple(bands), scheme=scheme, mode=mode, interpret=interpret
        )
    return _inv3d_math(list(bands), mode, scheme)  # see _fwd3d_level


def _resolve_3d(
    backend: Optional[str], d: int, h: int, w: int, scheme
) -> str:
    """Backend for a 3D transform; names the one remaining budget cliff."""
    b = _backend.resolve(backend)
    if b != "xla" and not _fits_vmem3(d, h, w) and not _can_slab(d, h, w, scheme):
        _backend.note_degrade(
            b, "xla",
            f"budget: ({d}, {h}, {w}) exceeds the whole-volume VMEM budget "
            f"and scheme {S.get_scheme(scheme).name!r} cannot take the "
            "depth-slab path there",
        )
        return "xla"
    return b


def plan_3d(
    d: int, h: int, w: int, backend: Optional[str] = None, scheme="cdf53"
) -> str:
    """Name the execution path a (d, h, w) 3D transform will take.

    One of ``whole-pallas`` / ``slab-pallas`` / ``whole-interpret`` /
    ``slab-interpret`` / ``xla``.  Benchmarks and the CI gate
    (``benchmarks/gate.py``) use this to assert budget-sized volumes
    never silently leave the Pallas path on an accelerator.
    """
    sch = S.get_scheme(scheme)
    b = _resolve_3d(backend, d, h, w, sch)
    if b == "xla":
        return "xla"
    kind = "slab" if _use_slab(d, h, w, sch) else "whole"
    return f"{kind}-{'interpret' if b == 'interpret' else 'pallas'}"


# ---------------------------------------------------------------------------
# Fused multi-level 3D pyramid (mirrors fused2d's multi-level dispatch).
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("levels", "scheme", "mode", "interpret", "dispatch")
)
def _fwd3d_multi_kernel(x, levels, scheme, mode, interpret, dispatch):
    # `dispatch` (backend.dispatch_state()) keys the jit cache on the env
    # overrides so REPRO_DWT_SLAB / REPRO_DWT_VMEM_MB retrace, not no-op
    approx = x.astype(_compute_dtype(x.dtype))  # in-jit: no eager host copy
    details: List[Tuple[Array, ...]] = []
    for _ in range(levels):
        bands = _fwd3d_level(approx, scheme, mode, interpret)
        approx = bands[0]
        details.append(tuple(bands[1:]))
    return approx, tuple(reversed(details))


def _fwd3d_multi_xla(x, levels, scheme, mode):
    # per-level jitted dispatches, NOT one fused program: same XLA:CPU
    # chained-graph compile cliff as fused2d._fwd2d_multi_xla
    approx = x
    details: List[Tuple[Array, ...]] = []
    for _ in range(levels):
        bands = _fwd3d_xla(approx, scheme=scheme, mode=mode)
        approx = bands[0]
        details.append(tuple(bands[1:]))
    return approx, tuple(reversed(details))


@functools.partial(
    jax.jit, static_argnames=("scheme", "mode", "interpret", "dispatch")
)
def _inv3d_multi_kernel(approx, details, scheme, mode, interpret, dispatch):
    cdt = _compute_dtype(approx.dtype)
    approx = approx.astype(cdt)
    for lvl in details:  # coarsest first
        bands = (approx,) + tuple(b.astype(cdt) for b in lvl)
        approx = _inv3d_level(bands, scheme, mode, interpret)
    return approx


def _inv3d_multi_xla(approx, details, scheme, mode):
    for lvl in details:  # per-level dispatch: see _fwd3d_multi_xla
        approx = _inv3d_xla((approx,) + tuple(lvl), scheme=scheme, mode=mode)
    return approx


# ---------------------------------------------------------------------------
# ndim=1/2 re-wrapping: the existing fused engines ARE the N-D engine for
# those ranks; only the band bookkeeping differs (code order).
# ---------------------------------------------------------------------------


def _fwd_nd_via_1d(x, levels, mode, backend, scheme) -> PyramidND:
    # checked=False throughout the via-helpers: dwt_fwd_nd/dwt_inv_nd
    # already ran the checked gate for the whole call
    pyr = _ops.dwt_fwd(
        x, levels=levels, mode=mode, backend=backend, scheme=scheme,
        checked=False,
    )
    return PyramidND(approx=pyr.approx, details=tuple((d,) for d in pyr.details))


def _inv_nd_via_1d(pyr: PyramidND, mode, backend, scheme):
    wp = _lift.WaveletPyramid(
        approx=pyr.approx, details=tuple(lvl[0] for lvl in pyr.details)
    )
    return _ops.dwt_inv(
        wp, mode=mode, backend=backend, scheme=scheme, checked=False
    )


def _fwd_nd_via_2d(x, levels, mode, backend, scheme) -> PyramidND:
    p2 = _f2d.dwt_fwd_2d_multi(
        x, levels=levels, mode=mode, backend=backend, scheme=scheme,
        checked=False,
    )
    # Pyramid2D stores (lh, hl, hh); code order is (hl, lh, hh) — bit 0
    # (highpass along -1) first
    return PyramidND(
        approx=p2.ll,
        details=tuple((hl, lh, hh) for lh, hl, hh in p2.details),
    )


def _inv_nd_via_2d(pyr: PyramidND, mode, backend, scheme):
    p2 = _lift.Pyramid2D(
        ll=pyr.approx,
        details=tuple((lvl[1], lvl[0], lvl[2]) for lvl in pyr.details),
    )
    return _f2d.dwt_inv_2d_multi(
        p2, mode=mode, backend=backend, scheme=scheme, checked=False
    )


# ---------------------------------------------------------------------------
# Generic ndim > 3: per-level jitted reference (exotic rank, no kernel).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("ndim", "scheme", "mode"))
def _fwd_nd_xla_level(x, ndim, scheme, mode):
    return tuple(
        _lift._fwd_nd_level(x.astype(_compute_dtype(x.dtype)), ndim, mode, scheme)
    )


@functools.partial(jax.jit, static_argnames=("ndim", "scheme", "mode"))
def _inv_nd_xla_level(bands, ndim, scheme, mode):
    cdt = _compute_dtype(bands[0].dtype)
    return _lift._inv_nd_level([b.astype(cdt) for b in bands], ndim, mode, scheme)


# ---------------------------------------------------------------------------
# Public API.
# ---------------------------------------------------------------------------


def dwt_fwd_nd(
    x: Array,
    levels: int = 1,
    mode: str = "paper",
    backend: Optional[str] = None,
    scheme="cdf53",
    ndim: int = 3,
    checked=None,
) -> PyramidND:
    """Fused multi-level N-D forward transform over the last ``ndim`` axes.

    ndim=3 is the first-class fused volume path (whole-volume Pallas
    kernel within the VMEM budget, depth-slab kernel beyond it); ndim=1/2
    reuse the existing fused engines; any registered scheme, any axis
    lengths >= 2 (``levels=0`` is the identity pyramid).  Bit-exact vs
    ``core.lifting.dwt_fwd_nd`` on every backend.  ``checked=True`` (or
    ``REPRO_DWT_CHECKED=1``) certifies the data against the derived
    range bounds and raises ``IntegerOverflowError`` instead of ever
    returning wrapped bands (``core/ranges.py``).
    """
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if ndim < 1:
        raise ValueError(f"ndim must be >= 1, got {ndim}")
    if x.ndim < ndim:
        raise ValueError(f"need >= {ndim} axes, got shape {x.shape}")
    check_levels_nd(x.shape[-ndim:], levels)
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked(
            lambda a: dwt_fwd_nd(a, levels=levels, mode=mode, backend=backend,
                                 scheme=sch, ndim=ndim, checked=False),
            x, scheme=sch, levels=levels, mode=mode, ndim=ndim,
            label="kernels.dwt_fwd_nd",
        )
    if ndim == 1:
        return _fwd_nd_via_1d(x, levels, mode, backend, sch)
    if ndim == 2:
        return _fwd_nd_via_2d(x, levels, mode, backend, sch)
    if ndim > 3:
        approx = x
        details: List[Tuple[Array, ...]] = []
        for _ in range(levels):
            bands = _fwd_nd_xla_level(approx, ndim=ndim, scheme=sch, mode=mode)
            approx = bands[0]
            details.append(tuple(bands[1:]))
        return PyramidND(approx=approx, details=tuple(reversed(details)))
    d, h, w = x.shape[-3:]
    b = _resolve_3d(backend, d, h, w, sch)
    lead = x.shape[:-3]

    def _kernel() -> PyramidND:
        xf = x.reshape((-1, d, h, w))  # metadata-only; promotion in-jit
        approx, details = _fwd3d_multi_kernel(
            xf, levels=levels, scheme=sch, mode=mode,
            interpret=_backend.interpret_flag(b),
            dispatch=_backend.dispatch_state(),
        )

        def unlead(a: Array) -> Array:
            return a.reshape(lead + a.shape[1:])

        return PyramidND(
            approx=unlead(approx),
            details=tuple(tuple(unlead(b_) for b_ in lvl) for lvl in details),
        )

    def _xla() -> PyramidND:
        approx, details = _fwd3d_multi_xla(
            x, levels=levels, scheme=sch, mode=mode
        )
        return PyramidND(approx=approx, details=details)

    return _backend.pallas_guard(b, "dwt_fwd_nd", x, _kernel, _xla)


def dwt_inv_nd(
    pyr: PyramidND,
    mode: str = "paper",
    backend: Optional[str] = None,
    scheme="cdf53",
    checked=None,
) -> Array:
    """Inverse of :func:`dwt_fwd_nd` (one fused dispatch on Pallas)."""
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if not pyr.details:
        return _lift.promote_narrow(pyr.approx)
    ndim = pyr.ndim  # validates the band count
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked_inv(
            lambda p: dwt_inv_nd(p, mode=mode, backend=backend, scheme=sch,
                                 checked=False),
            pyr, scheme=sch, levels=pyr.levels, mode=mode, ndim=ndim,
            label="kernels.dwt_inv_nd",
        )
    if ndim == 1:
        return _inv_nd_via_1d(pyr, mode, backend, sch)
    if ndim == 2:
        return _inv_nd_via_2d(pyr, mode, backend, sch)
    if ndim > 3:
        approx = pyr.approx
        for lvl in pyr.details:
            approx = _inv_nd_xla_level(
                (approx,) + tuple(lvl), ndim=ndim, scheme=sch, mode=mode
            )
        return approx
    # validate band geometry coarsest-first and recover the final shape
    d, h, w = pyr.approx.shape[-3:]
    for lvl in pyr.details:
        if len(lvl) != _N_BANDS_3D - 1:
            raise ValueError(
                f"3D pyramid level must carry 7 detail bands, got {len(lvl)}"
            )
        dims = _band_dims_3d(
            d + lvl[3].shape[-3], h + lvl[1].shape[-2], w + lvl[0].shape[-1]
        )
        for code in range(1, _N_BANDS_3D):
            if tuple(lvl[code - 1].shape[-3:]) != dims[code]:
                raise ValueError(
                    f"band shape mismatch at approx={(d, h, w)}: code {code} "
                    f"is {lvl[code - 1].shape[-3:]}, want {dims[code]}"
                )
        d, h, w = d + lvl[3].shape[-3], h + lvl[1].shape[-2], w + lvl[0].shape[-1]
    b = _resolve_3d(backend, d, h, w, sch)

    def _kernel() -> Array:
        lead = pyr.approx.shape[:-3]

        def flat(a: Array) -> Array:
            return a.reshape((-1,) + a.shape[len(lead):])  # metadata-only

        details = tuple(tuple(flat(b_) for b_ in lvl) for lvl in pyr.details)
        x = _inv3d_multi_kernel(
            flat(pyr.approx), details, scheme=sch, mode=mode,
            interpret=_backend.interpret_flag(b),
            dispatch=_backend.dispatch_state(),
        )
        return x.reshape(lead + x.shape[1:])

    return _backend.pallas_guard(
        b, "dwt_inv_nd", pyr, _kernel,
        lambda: _inv3d_multi_xla(
            pyr.approx, tuple(pyr.details), scheme=sch, mode=mode
        ),
    )
