"""Backend dispatch for the DWT kernels: compiled by default.

The seed threaded ``interpret=True`` through every kernel wrapper, so the
hot path ran the Pallas kernels under the (orders-of-magnitude slower)
interpreter on every platform.  This module probes the platform once and
resolves every transform call to one of three execution backends:

  ``pallas``     pl.pallas_call compiled by Mosaic — the default on TPU,
                 where the blocked VMEM dataflow pays off.  (GPU is
                 pallas-CAPABLE via Triton but defaults to xla until the
                 Triton lowering is validated; request it explicitly.)
  ``xla``        the paper-faithful jnp reference (``kernels/ref.py``)
                 under ``jax.jit`` — the default on CPU, where Pallas has
                 no compiled target and XLA fuses the lifting stencils
                 into tight vector loops.  Still "compiled by default".
  ``interpret``  pl.pallas_call with ``interpret=True`` — the Pallas
                 emulator.  Never a default: it exists for debugging the
                 kernel dataflow and as the automatic degrade when a
                 caller explicitly requests ``pallas`` on a platform
                 without a compiled Pallas target (CPU).

Resolution order for ``backend=None`` (every public wrapper's default):
``use_backend(...)`` context override > ``REPRO_DWT_BACKEND`` env var >
platform default (tpu/gpu -> pallas, else xla).

All three backends are bit-exact for every shape/dtype/mode — tests sweep
this — so dispatch is purely a performance decision.  See DESIGN.md §3.
"""
from __future__ import annotations

import contextlib
import functools
import os
import warnings
from typing import Iterator, Optional, Tuple

import jax

from repro import obs

VALID_BACKENDS = ("pallas", "xla", "interpret")

# "auto" in REPRO_DWT_BACKEND means: ignore the env var, use the platform
# default (handy for un-setting a sticky CI variable per-run).
_ENV_VAR = "REPRO_DWT_BACKEND"

_override: Optional[str] = None  # set by use_backend()

# platforms with SOME compiled Pallas lowering (Mosaic / Triton): an
# explicit backend="pallas" request on these runs compiled, not emulated
_PALLAS_CAPABLE = ("tpu", "gpu", "cuda", "rocm")

# platforms where compiled Pallas is the DEFAULT.  TPU only for now: the
# kernels are written against the Mosaic lowering; the GPU Triton
# lowering needs power-of-two block dims, which pick_blocks and the
# fused-2D per-image blocks do not guarantee, and CI never exercises it.
# GPU therefore defaults to the jitted XLA reference; opt in to Triton
# explicitly with backend="pallas" / REPRO_DWT_BACKEND=pallas once
# validated on the target stack.
_PALLAS_DEFAULT = ("tpu",)


@functools.lru_cache(maxsize=None)
def platform() -> str:
    """The default jax platform, probed once per process."""
    return jax.default_backend()


def has_compiled_pallas() -> bool:
    return platform() in _PALLAS_CAPABLE


def default_backend() -> str:
    """Platform/env default: compiled pallas on TPU, compiled XLA elsewhere."""
    env = os.environ.get(_ENV_VAR, "").strip().lower()
    if env and env != "auto":
        if env not in VALID_BACKENDS:
            raise ValueError(
                f"{_ENV_VAR}={env!r}: must be one of {VALID_BACKENDS} or 'auto'"
            )
        return env
    return "pallas" if platform() in _PALLAS_DEFAULT else "xla"


def resolve_backend(
    backend: Optional[str] = None, *, explain: bool = False
):
    """Resolve a per-call ``backend=`` argument to an executable backend.

    ``None`` defers to the context override / env var / platform default.
    An explicit ``pallas`` request on a platform without a compiled Pallas
    target degrades to ``interpret`` (same kernels, emulated) so kernel
    code paths stay testable everywhere.

    With ``explain=True`` returns ``(resolved, reason)`` where ``reason``
    names why the request landed where it did — tests and the smoke gate
    use this to assert that no production shape silently leaves the
    compiled Pallas path on an accelerator.
    """
    name = backend or _override or default_backend()
    if name not in VALID_BACKENDS:
        raise ValueError(f"backend must be one of {VALID_BACKENDS}, got {name!r}")
    if name == "pallas" and not has_compiled_pallas():
        note_degrade(
            "pallas", "interpret",
            "off-accelerator: no compiled Pallas target on "
            f"platform={platform()!r}; running the same kernels emulated",
        )
        _note_dispatch(name, "interpret", "degraded:off-accelerator")
        return ("interpret", "degraded:off-accelerator") if explain else "interpret"
    if backend:
        reason = "explicit"
    elif _override:
        reason = "context-override"
    elif os.environ.get(_ENV_VAR, "").strip().lower() not in ("", "auto"):
        reason = "env-var"
    else:
        reason = "platform-default"
    _note_dispatch(backend or "", name, reason)
    return (name, reason) if explain else name


def resolve(backend: Optional[str] = None) -> str:
    """Back-compat alias for :func:`resolve_backend` (name only)."""
    return resolve_backend(backend)


class BackendDegradeWarning(RuntimeWarning):
    """A backend request silently degraded (pallas -> interpret
    off-accelerator, pallas -> xla for an untileable shape, ...).

    A dedicated category so operators can filter or escalate degrade
    notices independently of generic RuntimeWarnings: the tier-1 suite
    ignores exactly this category (tests/conftest.py) while the CI smoke
    gate runs with all other RuntimeWarnings as errors.
    """


# one-time degrade warnings: a silently-degraded request warns ONCE per
# distinct (requested, resolved, reason) so production logs name the
# cliff without spamming per-call.  The metrics registry counts EVERY
# occurrence (obs counter ``kernels.degrades``) and the event log gets a
# DegradeEvent per occurrence — dedupe applies to the warning only.
_warned_degrades: set = set()

# dispatch DECISIONS land in the event log once per distinct outcome;
# dispatch VOLUME is the ``kernels.dispatch`` counter (per-call events
# would crowd real transitions out of the bounded ring).
_seen_dispatches: set = set()


def _note_dispatch(requested: str, resolved: str, reason: str) -> None:
    obs.counter("kernels.dispatch", resolved=resolved, reason=reason).inc()
    key = (requested, resolved, reason)
    if key not in _seen_dispatches:
        _seen_dispatches.add(key)
        obs.emit(obs.DispatchEvent(
            subsystem="kernels", requested=requested, resolved=resolved,
            reason=reason,
        ))


def note_degrade(requested: str, resolved: str, reason: str) -> None:
    """Record a degrade: count + event EVERY time, warn once per key.

    The counter answers "how many times has this path degraded" (lost
    under the old one-shot dedupe); the warning still fires exactly once
    per distinct (requested, resolved, reason) so logs stay readable.
    """
    obs.counter("kernels.degrades", requested=requested, resolved=resolved).inc()
    obs.emit(obs.DegradeEvent(
        subsystem="kernels", requested=requested, resolved=resolved,
        reason=reason,
    ))
    key = (requested, resolved, reason)
    if key in _warned_degrades:
        return
    _warned_degrades.add(key)
    warnings.warn(
        f"DWT backend request {requested!r} degraded to {resolved!r}: {reason}",
        BackendDegradeWarning,
        stacklevel=3,
    )


def _host_span(label: str, operand):
    """A kernels-subsystem span — but ONLY outside any jax trace.

    ``pallas_guard`` runs both host-side (direct wrapper calls) and at
    trace time (under a caller's ``jax.jit``); a span recorded during
    tracing would measure compile time once and nothing thereafter, so
    when the call's operand is a tracer this is a null context instead.
    """
    if any(isinstance(a, jax.core.Tracer) for a in jax.tree_util.tree_leaves(operand)):
        return contextlib.nullcontext()
    return obs.span(label, subsystem="kernels")


def pallas_guard(resolved: str, label: str, operand, kernel_thunk, xla_thunk):
    """Run a transform on the path ``resolved`` names — and only there.

    The single choke point every public wrapper dispatches through: the
    XLA reference thunk on ``xla``, the Pallas thunk otherwise.  There is
    no fallback: a kernel that fails to lower, compile or launch (a
    Mosaic refusal, an emulator fault, an injected ``kernels.pallas``
    chaos fault) records a ``FaultEvent`` and raises.  Both paths are
    bit-exact by construction, so a fallback could only ever trade
    speed — and a silently slower path is exactly what a benchmark must
    never time.  ``operand`` (the call's input array or pytree) tells a
    host call from one traced under a caller's jit, for the span.
    Argument-validation errors are raised by the wrappers BEFORE
    dispatch and never reach this guard.
    """
    from repro.resilience import inject

    with _host_span(label, operand):
        if resolved == "xla":
            return xla_thunk()
        try:
            inject.check("kernels.pallas")
            return kernel_thunk()
        except Exception as e:
            obs.emit(obs.FaultEvent(
                subsystem="kernels", error=type(e).__name__, site=label,
            ))
            raise


@contextlib.contextmanager
def use_backend(name: str) -> Iterator[None]:
    """Force a backend for every kernel call in scope (tests/benchmarks).

    Caveat: the backend is resolved at TRACE time.  If a caller's
    ``jax.jit`` first traces a transform inside this context, the choice
    is baked into that trace's cache and persists for same-shape calls
    after the context exits.  Scope overrides around whole workloads (or
    use distinct jitted callables), not around individual calls inside a
    long-lived jit.
    """
    global _override
    if name not in VALID_BACKENDS:
        raise ValueError(f"backend must be one of {VALID_BACKENDS}, got {name!r}")
    prev, _override = _override, name
    try:
        yield
    finally:
        _override = prev


def interpret_flag(resolved: str) -> bool:
    """The ``interpret=`` flag for pl.pallas_call under a resolved backend."""
    return resolved == "interpret"


# ---------------------------------------------------------------------------
# Block-size selection (DESIGN.md §3): VPU-shaped tiles, shrunk to fit.
# ---------------------------------------------------------------------------

# default tile: 8 sublanes x 256 lanes per polyphase stream — one VPU
# (8, 128) register pair per int32 stream tile, small enough that the six
# resident streams of the fused kernels stay well under VMEM.
DEFAULT_BLOCK_ROWS = 8
DEFAULT_BLOCK_PAIRS = 256


def pick_blocks(n_rows: int, n_pairs: int) -> Tuple[int, int]:
    """(block_rows, block_pairs) for a (rows, pairs) polyphase stream."""
    return (
        min(DEFAULT_BLOCK_ROWS, n_rows),
        min(DEFAULT_BLOCK_PAIRS, n_pairs),
    )


# ---------------------------------------------------------------------------
# VMEM budget + fused-2D whole-image / tiled policy (DESIGN.md §5-6).
#
# The budget is DERIVED from the queried device, not hard-coded: Pallas
# blocks live in VMEM (~16MB/core on every shipping TPU), so the probe
# asks the device for ``core_on_chip_memory_size`` when it exposes one and
# falls back to the architectural 16MB otherwise.  ``memory_stats()``
# (HBM) bounds it from above on exotic hosts.  ``REPRO_DWT_VMEM_MB``
# overrides the probe; results are cached per process.
# ---------------------------------------------------------------------------

_VMEM_ENV = "REPRO_DWT_VMEM_MB"
_TILE_ENV = "REPRO_DWT_TILE"

_DEFAULT_VMEM_BYTES = 16 * 1024 * 1024

# the fused whole-image 2D kernel keeps ~6 image-sized int32 buffers
# resident per grid cell (input, 2 row streams, 4 subbands, sliced)
FUSED2D_RESIDENT_BUFFERS = 6


def vmem_budget_bytes() -> int:
    """Per-core fast-memory budget for resident kernel buffers (bytes).

    Cached per env state: a changed ``REPRO_DWT_VMEM_MB`` takes effect
    immediately (no manual cache clearing).
    """
    return _vmem_budget_bytes(os.environ.get(_VMEM_ENV, "").strip())


@functools.lru_cache(maxsize=None)
def _vmem_budget_bytes(env: str) -> int:
    if env:
        return int(float(env) * 1024 * 1024)
    dev = jax.devices()[0]
    # TPU backends expose the on-chip memory size; others don't.
    for attr in ("core_on_chip_memory_size", "vmem_size_bytes"):
        size = getattr(dev, attr, None)
        if isinstance(size, int) and size > 0:
            return size
    try:
        stats = dev.memory_stats()
    except Exception:  # noqa: BLE001 - CPU backends raise/return None
        stats = None
    if stats and stats.get("bytes_limit"):
        # no VMEM concept (cpu/gpu fallback): cap the *blocked* working
        # set at the architectural 16MB so tile maths stay TPU-shaped
        return min(int(stats["bytes_limit"]), _DEFAULT_VMEM_BYTES)
    return _DEFAULT_VMEM_BYTES


def fused2d_budget_elems() -> int:
    """Largest per-image element count the whole-image 2D kernel accepts.

    Derived from :func:`vmem_budget_bytes`: ~6 resident int32 image-sized
    buffers per grid cell, with 2x headroom for Mosaic spills.
    """
    return max(
        vmem_budget_bytes() // (4 * FUSED2D_RESIDENT_BUFFERS * 2),
        8 * 1024,
    )


# the fused whole-volume 3D kernel keeps ~10 volume-sized int32 buffers
# resident per grid cell (input, 2 row streams, 4 plane bands, then the
# 8 subband octants overlap the freed intermediates)
FUSED3D_RESIDENT_BUFFERS = 10


def fused3d_budget_elems() -> int:
    """Largest per-volume element count the whole-volume 3D kernel accepts.

    Derived from :func:`vmem_budget_bytes` like the 2D budget, with the
    deeper resident-buffer count of the three-axis cascade.
    """
    return max(
        vmem_budget_bytes() // (4 * FUSED3D_RESIDENT_BUFFERS * 2),
        4 * 1024,
    )


# tiled-2D engine default core tile: 256 samples makes every band tile
# (128, 128) — Mosaic requires a block that is not the whole array to be
# (8, 128)-aligned in its two minor dims.  The halo rides in the window
# input, whose block is always the whole window.
DEFAULT_TILE = 256
_MIN_TILE = 4  # tiles are even and >= 4 so every window has a full halo


def tile_forced() -> bool:
    """True when ``REPRO_DWT_TILE`` is set: the tiled engine is forced for
    every tileable image, budget or not (tuning + the test lever that
    exercises multi-tile grids on small images)."""
    return bool(os.environ.get(_TILE_ENV, "").strip())


def _tile_env_override() -> Optional[Tuple[int, int]]:
    env = os.environ.get(_TILE_ENV, "").strip()
    if not env:
        return None
    parts = [p for p in env.replace("x", ",").split(",") if p]
    try:
        vals = [int(p) for p in parts]
    except ValueError as e:
        raise ValueError(
            f"{_TILE_ENV}={env!r}: expected 'N' or 'TH,TW' integers"
        ) from e
    th, tw = (vals[0], vals[0]) if len(vals) == 1 else (vals[0], vals[1])
    if th < _MIN_TILE or tw < _MIN_TILE or th % 2 or tw % 2:
        raise ValueError(
            f"{_TILE_ENV}={env!r}: tile dims must be even and >= {_MIN_TILE}"
        )
    return th, tw


def dispatch_state() -> Tuple[str, str, str]:
    """The env-derived dispatch inputs, as a hashable token.

    Threaded as a static argument through the multi-level jit wrappers so
    changing ``REPRO_DWT_TILE`` / ``REPRO_DWT_VMEM_MB`` /
    ``REPRO_DWT_SLAB`` mid-process retraces instead of silently reusing
    an executable whose whole-image vs tiled/slab choices were baked
    under the old state.
    """
    return (
        os.environ.get(_TILE_ENV, "").strip(),
        os.environ.get(_VMEM_ENV, "").strip(),
        os.environ.get(_SLAB_ENV, "").strip(),
    )


def pick_tile(h: int, w: int, halo: int = 2) -> Tuple[int, int]:
    """(TH, TW) core-tile shape for a tiled 2D transform of an (h, w) image.

    ``halo`` is the scheme-derived reflect-halo width in samples per side
    (``LiftingScheme.halo``; 2 for the paper's cdf53, 4 for 97m, 0 for
    haar) — it enters the VMEM window budget as (TH+2*halo)*(TW+2*halo).
    Cached per (shape, halo, env state).  ``REPRO_DWT_TILE`` ("N" or
    "TH,TW") overrides — the escape hatch for tuning and the lever tests
    use to exercise multi-tile grids on small images.  Chosen tiles are
    even, at least ``_MIN_TILE``, and sized so the ~6 resident
    window-sized buffers of the tiled kernels fit the derived budget.
    """
    return _pick_tile(h, w, halo, dispatch_state())


@functools.lru_cache(maxsize=4096)
def _pick_tile(h: int, w: int, halo: int, _state) -> Tuple[int, int]:
    override = _tile_env_override()
    if override is not None:
        return override
    budget = fused2d_budget_elems()
    th = tw = DEFAULT_TILE
    # shrink square-ish until the halo'd window set fits the budget
    while (th + 2 * halo) * (tw + 2 * halo) > budget and th > _MIN_TILE:
        th = max(th // 2 - (th // 2) % 2, _MIN_TILE)
        tw = th
    # never tile beyond the image (ceil to even: odd dims get one pad col)
    th = min(th, h + (h % 2))
    tw = min(tw, w + (w % 2))
    return max(th, _MIN_TILE), max(tw, _MIN_TILE)


# ---------------------------------------------------------------------------
# Slab policy for the fused 3D engine (kernels/fused3d.py): volumes past
# the whole-volume budget are blocked along the DEPTH axis only — a slab
# of TD depth slices plus the scheme's reflect halo, with H and W kept
# fully resident per slab (the plane axes run the exact band-policy
# math, so any registered scheme works along them; only the slab axis
# needs windowability).
# ---------------------------------------------------------------------------

_SLAB_ENV = "REPRO_DWT_SLAB"

DEFAULT_SLAB = 8  # depth slices per slab core; shrunk to fit the budget
_MIN_SLAB = 2  # slabs are even and >= 2 so every window has a full halo


def slab_forced() -> bool:
    """True when ``REPRO_DWT_SLAB`` is set: the slab-tiled 3D engine is
    forced for every slab-able volume, budget or not (tuning + the test
    lever that exercises multi-slab grids on small volumes)."""
    return bool(os.environ.get(_SLAB_ENV, "").strip())


def _slab_env_override() -> Optional[int]:
    env = os.environ.get(_SLAB_ENV, "").strip()
    if not env:
        return None
    try:
        td = int(env)
    except ValueError as e:
        raise ValueError(f"{_SLAB_ENV}={env!r}: expected an integer") from e
    if td < _MIN_SLAB or td % 2:
        raise ValueError(
            f"{_SLAB_ENV}={env!r}: slab depth must be even and >= {_MIN_SLAB}"
        )
    return td


def pick_slab(d: int, h: int, w: int, halo: int = 2) -> int:
    """Core slab depth TD for a (d, h, w) volume under the 3D budget.

    Even, >= ``_MIN_SLAB``, sized so the halo'd (TD + 2*halo, H, W) slab
    windows (the dominant resident buffers of the slab kernel) fit the
    derived budget.  ``REPRO_DWT_SLAB`` overrides.
    """
    return _pick_slab(d, h, w, halo, dispatch_state())


@functools.lru_cache(maxsize=4096)
def _pick_slab(d: int, h: int, w: int, halo: int, _state) -> int:
    override = _slab_env_override()
    if override is not None:
        return override
    budget = fused3d_budget_elems()
    td = DEFAULT_SLAB
    while (td + 2 * halo) * h * w > budget and td > _MIN_SLAB:
        td = max(td - 2, _MIN_SLAB)
    # never slab beyond the volume (ceil to even: odd depth pads one slice)
    td = min(td, d + (d % 2))
    return max(td, _MIN_SLAB)


def slab_fits(h: int, w: int, halo: int = 2) -> bool:
    """True when even the minimal slab window fits the 3D budget — the
    feasibility half of the slab-vs-XLA fallback decision."""
    if _slab_env_override() is not None:
        return True  # explicit override: the operator owns the budget
    return (_MIN_SLAB + 2 * halo) * h * w <= fused3d_budget_elems()
