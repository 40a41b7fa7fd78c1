"""Multi-device 2D DWT: rows spatially sharded, halos via ``ppermute``.

The paper's parallel lifting architecture needs only a small boundary
overlap between neighboring PEs — ``scheme.halo`` samples, derived from
the scheme's step supports — and across devices that overlap becomes an
explicit halo exchange.  This module runs the full multi-level 2D Mallat
pyramid under ``shard_map`` with the image's row axis sharded over a
mesh axis (``data`` by default, via the same logical-rules machinery as
the rest of the system — ``sharding.spec_for``):

  * The row-direction (width) lifting is device-local: each shard holds
    full rows and runs the band-policy reference math
    (``schemes.lift_fwd_axis``) along the unsharded last axis — any
    scheme, any width parity.
  * The column-direction lifting needs ``scheme.halo`` rows from each
    spatial neighbor per level (2 for the paper's cdf53, 4 for 97m, none
    for haar).  Both row-transformed streams (s_r | d_r, together
    exactly one image row wide) are exchanged in a single ``ppermute``
    per direction per level.  Global edges swap the received halo for
    whole-point reflect rows computed locally, so the boundary policy
    matches the reference exactly (same identity the tiled engine rests
    on — hence the scheme gate: steps must commute with reflection, or
    exchange nothing at all).
  * The inverse exchanges ``scheme.inv_margin`` band-rows of all four
    subbands per direction per level and swaps global edges for the
    band-policy rows (``schemes.reflect_entry`` patterns).

Local compute reuses the interior-math primitives of
``core/schemes.py`` (the same functions that run inside the Pallas
kernels), so the sharded transform is bit-exact vs the single-device
engine — the tier-1 CPU-mesh test asserts it per scheme.  Shapes: H must
divide by ``axis_size * 2**levels`` with enough local rows for the
scheme's halo at the coarsest level; W >= 3 at every level (any parity).

See DESIGN.md §7 for the communication pattern.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro import obs
from repro import sharding as SH
from repro.core import ranges as _ranges
from repro.core import schemes as S
from repro.core.lifting import Pyramid2D, _check_mode
from repro.kernels.ops import _compute_dtype
from repro.resilience import inject
from repro.resilience.errors import CollectiveTimeoutError

Array = jax.Array


def _watchdogged(thunk, label: str, timeout_s: Optional[float]):
    """Run a collective-bearing thunk under a host-side completion watchdog.

    XLA collectives cannot be interrupted in-process, and a stuck mesh
    neighbor (dead host, wedged interconnect) hangs ``ppermute`` — and
    therefore the caller — forever.  The thunk runs (and is blocked to
    completion) on a daemon worker thread; if it has not completed within
    ``timeout_s`` the host raises :class:`CollectiveTimeoutError` naming
    the transform, so the controller can evict/reshard instead of
    hanging.  The orphaned worker is a daemon: it cannot keep a dying
    process alive, which is the strongest guarantee available without
    runtime-level collective abort.  ``timeout_s=None`` (default) runs
    inline with no watchdog thread — the zero-overhead fast path.

    The ``sharded.collective`` inject site sits inside the timed region,
    so the chaos suite can simulate the stuck neighbor deterministically
    (a delay fault) without a real multi-host hang.
    """
    if timeout_s is None:
        # fast path: host dispatch only — the span measures launch cost,
        # never a device sync (async dispatch returns before completion)
        with obs.span(label, subsystem="collectives"):
            inject.check("sharded.collective")
            return thunk()
    result: list = []
    failure: list = []

    def _run():
        try:
            inject.check("sharded.collective")
            out = thunk()
            result.append(jax.block_until_ready(out))
        except BaseException as e:  # surfaced below on the caller thread
            failure.append(e)

    worker = threading.Thread(target=_run, daemon=True)
    t0 = time.perf_counter()
    # watchdogged path: the worker blocks to completion anyway (that is
    # the watchdog's job), so the span IS end-to-end collective time
    with obs.span(label, subsystem="collectives", timeout_s=timeout_s):
        worker.start()
        worker.join(timeout_s)
    if worker.is_alive():
        obs.counter("collectives.watchdog_trips").inc()
        obs.emit(obs.FaultEvent(
            subsystem="collectives", error="CollectiveTimeoutError",
            site=label, detail=f"no completion within {timeout_s}s",
        ))
        raise CollectiveTimeoutError(
            f"{label}: collective did not complete within {timeout_s}s — "
            "a mesh participant looks stuck (dead host or wedged "
            "interconnect); evict or reshard before retrying"
        )
    obs.histogram("collectives.exchange_ms").observe(
        (time.perf_counter() - t0) * 1e3
    )
    if failure:
        raise failure[0]
    return result[0]


def _scheme_shardable(sch: S.LiftingScheme) -> bool:
    # the column stage's exchanged-halo interior math must reproduce the
    # band policy: reflection-commuting steps, or no halo at all (haar —
    # column lengths are even by the divisibility constraint)
    return sch.symmetric or sch.halo == 0


def check_shardable(
    h: int, w: int, n_shards: int, levels: int, scheme="cdf53"
) -> None:
    """Raise unless (h, w) supports a row-sharded `levels`-deep pyramid."""
    sch = S.get_scheme(scheme)
    if levels < 1:
        raise ValueError("levels must be >= 1")
    if not _scheme_shardable(sch):
        raise ValueError(
            f"scheme {sch.name!r} has reflection-asymmetric steps and no "
            "halo-free form; the sharded engine cannot reproduce its "
            "boundary policy — use the fused 2D engine instead"
        )
    wl = w
    for _ in range(levels):
        if wl < 3:
            raise ValueError(
                f"sharded transform needs W >= 3 at every level, got W={w} "
                f"({wl} at some level) for levels={levels}"
            )
        wl = wl - wl // 2
    step = n_shards << levels
    min_local = max(4, sch.halo + 2)  # coarsest-level local rows floor
    if h % step or 2 * (h // step) < min_local:
        raise ValueError(
            f"sharded transform needs H divisible by axis_size * 2**levels "
            f"with >= {min_local} local rows at the coarsest level; got "
            f"H={h}, axis_size={n_shards}, levels={levels}, "
            f"scheme={sch.name!r} (halo={sch.halo})"
        )


def _rows(x: Array, start: int, stop: int) -> Array:
    return jax.lax.slice_in_dim(x, start, stop, axis=-2)


def _pick_rows(x: Array, idx) -> Array:
    """Concatenate single rows of ``x`` in the given (static) order."""
    return jnp.concatenate([_rows(x, i, i + 1) for i in idx], axis=-2)


def _exchange_rows(
    top_send: Array,
    bot_send: Array,
    axis: str,
    n: int,
    top_edge: Array,
    bot_edge: Array,
) -> Tuple[Array, Array]:
    """Swap border rows with spatial neighbors; edges take the given rows.

    Device i receives ``bot_send`` of device i-1 (its top halo) and
    ``top_send`` of device i+1 (its bottom halo).  One ppermute per
    direction; the wire carries exactly the border rows.
    """
    idx = jax.lax.axis_index(axis)
    down = [(i, i + 1) for i in range(n - 1)]
    up = [(i + 1, i) for i in range(n - 1)]
    recv_top = jax.lax.ppermute(bot_send, axis, down)
    recv_bot = jax.lax.ppermute(top_send, axis, up)
    top = jnp.where(idx == 0, top_edge, recv_top)
    bot = jnp.where(idx == n - 1, bot_edge, recv_bot)
    return top, bot


def _fwd_level_local(x: Array, scheme: str, mode: str, axis: str, n: int):
    """One forward 2D level on a row shard, exchanging halo rows."""
    sch = S.get_scheme(scheme)
    halo = sch.halo
    w = x.shape[-1]
    w_e = w - w // 2
    # width stage: device-local band-policy reference math (full rows)
    s_r, d_r = S.lift_fwd_axis(x, scheme, axis=-1, mode=mode)
    if halo == 0:
        s_ext, d_ext = s_r, d_r
    else:
        h_loc = s_r.shape[-2]
        border = jnp.concatenate  # one buffer per direction: s_r | d_r
        top_send = border([_rows(s_r, 0, halo), _rows(d_r, 0, halo)], axis=-1)
        bot_send = border(
            [_rows(s_r, h_loc - halo, h_loc), _rows(d_r, h_loc - halo, h_loc)],
            axis=-1,
        )
        # global-edge whole-point reflect rows, computed locally (only
        # read on shards 0 / n-1): top entries [-halo..-1] -> [halo..1],
        # bottom entries [H..H+halo-1] -> [H-2..H-halo-1]
        top_idx = list(range(halo, 0, -1))
        bot_idx = [h_loc - 2 - j for j in range(halo)]
        top_edge = border([_pick_rows(s_r, top_idx), _pick_rows(d_r, top_idx)], axis=-1)
        bot_edge = border([_pick_rows(s_r, bot_idx), _pick_rows(d_r, bot_idx)], axis=-1)
        top, bot = _exchange_rows(top_send, bot_send, axis, n, top_edge, bot_edge)
        s_ext = jnp.concatenate([top[..., :w_e], s_r, bot[..., :w_e]], axis=-2)
        d_ext = jnp.concatenate([top[..., w_e:], d_r, bot[..., w_e:]], axis=-2)
    ll, lh = S.lift_fwd_axis_ext(s_ext, scheme, axis=-2, mode=mode)
    hl, hh = S.lift_fwd_axis_ext(d_ext, scheme, axis=-2, mode=mode)
    return ll, lh, hl, hh


def _inv_level_local(
    ll: Array, lh: Array, hl: Array, hh: Array,
    scheme: str, mode: str, axis: str, n: int,
):
    """One inverse 2D level on row-sharded bands (inv_margin band-rows)."""
    sch = S.get_scheme(scheme)
    m = sch.inv_margin
    bands = (ll, lh, hl, hh)
    if m == 0:
        ext = bands
    else:
        n_loc = ll.shape[-2]
        widths = [b.shape[-1] for b in bands]
        top_send = jnp.concatenate([_rows(b, 0, m) for b in bands], axis=-1)
        bot_send = jnp.concatenate(
            [_rows(b, n_loc - m, n_loc) for b in bands], axis=-1
        )
        # global-edge band-policy rows (column length even by
        # construction): s-role (ll, hl): entries [-j] -> [j],
        # [n_e+j] -> [n_e-1-j]; d-role (lh, hh): [-j] -> [j-1],
        # [n_o+j] -> [n_o-2-j] — reflect_entry's whole-point patterns.
        s_top = list(range(m, 0, -1))
        d_top = list(range(m - 1, -1, -1))
        s_bot = [n_loc - 1 - j for j in range(m)]
        d_bot = [n_loc - 2 - j for j in range(m)]
        roles = ("s", "d", "s", "d")  # rows of ll/hl are s-role, lh/hh d-role
        top_edge = jnp.concatenate(
            [
                _pick_rows(b, s_top if r == "s" else d_top)
                for b, r in zip(bands, roles)
            ],
            axis=-1,
        )
        bot_edge = jnp.concatenate(
            [
                _pick_rows(b, s_bot if r == "s" else d_bot)
                for b, r in zip(bands, roles)
            ],
            axis=-1,
        )
        top, bot = _exchange_rows(top_send, bot_send, axis, n, top_edge, bot_edge)
        ext = []
        off = 0
        for b, wd in zip(bands, widths):
            ext.append(
                jnp.concatenate(
                    [top[..., off : off + wd], b, bot[..., off : off + wd]],
                    axis=-2,
                )
            )
            off += wd
        ext = tuple(ext)
    ll_e, lh_e, hl_e, hh_e = ext
    s_r = S.lift_inv_axis_ext(ll_e, lh_e, scheme, axis=-2, mode=mode)
    d_r = S.lift_inv_axis_ext(hl_e, hh_e, scheme, axis=-2, mode=mode)
    # width stage: device-local band-policy inverse (full rows)
    return S.lift_inv_axis(s_r, d_r, scheme, axis=-1, mode=mode)


# ---------------------------------------------------------------------------
# shard_map wrappers (cached per (mesh, axis, levels, mode, scheme, ndim)).
# ---------------------------------------------------------------------------


def _row_spec(ndim: int, axis: str):
    """PartitionSpec sharding the row (-2) axis, via sharding.py rules."""
    rules = {"rows": axis}
    axes = (None,) * (ndim - 2) + ("rows", None)
    return SH.spec_for(axes, rules)


@functools.lru_cache(maxsize=None)
def _fwd_sharded_fn(
    mesh: Mesh, axis: str, levels: int, mode: str, scheme: str, ndim: int
):
    n = mesh.shape[axis]
    spec = _row_spec(ndim, axis)
    out_specs = Pyramid2D(
        ll=spec, details=tuple((spec, spec, spec) for _ in range(levels))
    )

    def local_fwd(x_loc: Array) -> Pyramid2D:
        ll = x_loc
        details = []
        for _ in range(levels):
            ll, lh, hl, hh = _fwd_level_local(ll, scheme, mode, axis, n)
            details.append((lh, hl, hh))
        return Pyramid2D(ll=ll, details=tuple(reversed(details)))

    return jax.jit(
        jax.shard_map(local_fwd, mesh=mesh, in_specs=(spec,), out_specs=out_specs)
    )


@functools.lru_cache(maxsize=None)
def _inv_sharded_fn(
    mesh: Mesh, axis: str, levels: int, mode: str, scheme: str, ndim: int
):
    n = mesh.shape[axis]
    spec = _row_spec(ndim, axis)
    in_specs = (
        Pyramid2D(
            ll=spec, details=tuple((spec, spec, spec) for _ in range(levels))
        ),
    )

    def local_inv(pyr: Pyramid2D) -> Array:
        ll = pyr.ll
        for lh, hl, hh in pyr.details:  # coarsest first
            ll = _inv_level_local(ll, lh, hl, hh, scheme, mode, axis, n)
        return ll

    return jax.jit(
        jax.shard_map(local_inv, mesh=mesh, in_specs=in_specs, out_specs=spec)
    )


def dwt_fwd_2d_sharded(
    x: Array,
    mesh: Mesh,
    levels: int = 1,
    mode: str = "paper",
    axis: str = "data",
    backend: Optional[str] = None,  # noqa: ARG001 - reserved: local compute
    # is the kernels' own interior math under XLA inside shard_map; a
    # per-shard Pallas routing lands behind the same flag when validated
    scheme="cdf53",
    timeout_s: Optional[float] = None,
    checked=None,
) -> Pyramid2D:
    """Row-sharded multi-level 2D forward transform over ``mesh[axis]``.

    Bit-exact vs :func:`repro.kernels.dwt_fwd_2d_multi` for the same
    scheme; only the scheme's halo rows move between devices (one
    ppermute per direction per level).  ``timeout_s`` arms a host-side
    collective watchdog: a stuck mesh neighbor surfaces as
    :class:`~repro.resilience.errors.CollectiveTimeoutError` instead of
    hanging the caller forever.  ``checked=True`` (or
    ``REPRO_DWT_CHECKED=1``) certifies the data against the derived
    range bounds and raises ``IntegerOverflowError`` instead of ever
    returning wrapped bands (``core/ranges.py``).
    """
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if x.ndim < 2:
        raise ValueError(f"need a (..., H, W) input, got {x.shape}")
    check_shardable(x.shape[-2], x.shape[-1], mesh.shape[axis], levels, sch)
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked(
            lambda a: dwt_fwd_2d_sharded(
                a, mesh, levels=levels, mode=mode, axis=axis, backend=backend,
                scheme=sch, timeout_s=timeout_s, checked=False,
            ),
            x, scheme=sch, levels=levels, mode=mode, ndim=2,
            label="kernels.dwt_fwd_2d_sharded",
        )
    fn = _fwd_sharded_fn(mesh, axis, levels, mode, sch, x.ndim)
    return _watchdogged(
        lambda: fn(x.astype(_compute_dtype(x.dtype))),
        "dwt_fwd_2d_sharded", timeout_s,
    )


def dwt_inv_2d_sharded(
    pyr: Pyramid2D,
    mesh: Mesh,
    mode: str = "paper",
    axis: str = "data",
    backend: Optional[str] = None,  # noqa: ARG001 - see dwt_fwd_2d_sharded
    scheme="cdf53",
    timeout_s: Optional[float] = None,
    checked=None,
) -> Array:
    """Inverse of :func:`dwt_fwd_2d_sharded` (same exchange pattern,
    same optional collective watchdog)."""
    _check_mode(mode)
    sch = S.get_scheme(scheme)
    if _ranges.checked_enabled(checked):
        return _ranges.run_checked_inv(
            lambda p: dwt_inv_2d_sharded(
                p, mesh, mode=mode, axis=axis, backend=backend, scheme=sch,
                timeout_s=timeout_s, checked=False,
            ),
            pyr, scheme=sch, levels=len(pyr.details), mode=mode, ndim=2,
            label="kernels.dwt_inv_2d_sharded",
        )
    levels = len(pyr.details)
    h = pyr.ll.shape[-2] * (1 << levels)
    w = pyr.ll.shape[-1]
    for lh, hl, _hh in pyr.details:
        w = w + hl.shape[-1]
    check_shardable(h, w, mesh.shape[axis], levels, sch)
    cdt = _compute_dtype(pyr.ll.dtype)
    fn = _inv_sharded_fn(mesh, axis, levels, mode, sch, pyr.ll.ndim)
    cast = Pyramid2D(
        ll=pyr.ll.astype(cdt),
        details=tuple(
            (lh.astype(cdt), hl.astype(cdt), hh.astype(cdt))
            for lh, hl, hh in pyr.details
        ),
    )
    return _watchdogged(lambda: fn(cast), "dwt_inv_2d_sharded", timeout_s)


# ---------------------------------------------------------------------------
# (5,3) aliases — the seed's public names; nothing downstream breaks.
# ---------------------------------------------------------------------------


def dwt53_fwd_2d_sharded(
    x: Array,
    mesh: Mesh,
    levels: int = 1,
    mode: str = "paper",
    axis: str = "data",
    backend: Optional[str] = None,
) -> Pyramid2D:
    return dwt_fwd_2d_sharded(
        x, mesh, levels=levels, mode=mode, axis=axis, backend=backend,
        scheme="cdf53",
    )


def dwt53_inv_2d_sharded(
    pyr: Pyramid2D,
    mesh: Mesh,
    mode: str = "paper",
    axis: str = "data",
    backend: Optional[str] = None,
) -> Array:
    return dwt_inv_2d_sharded(
        pyr, mesh, mode=mode, axis=axis, backend=backend, scheme="cdf53"
    )
