"""Compiled-executable cache for the wavelet serve tier.

The execution half of the layered service core (DESIGN.md §14).  Every
``(bucket, scheme, levels, mode, backend, mesh)`` combination the
scheduler can emit maps to exactly ONE compiled executable, built on
first use and reused for the life of the engine — an admission, a
bucket switch, or a drained-and-refilled queue never recompiles.  The
cache is the serve-tier analogue of the LM engine's jit-once prefill
fix (PR 7): the regression it guards against (a fresh ``jax.jit``
wrapper per step, retracing the transform graph on every micro-batch)
costs 100-1000x on real configs and is invisible to correctness tests.

Two things make the cache sound:

  * **Static keys** — the batch shape is pinned by the bucket and the
    engine's ``batch_slots``, so a key's executable serves every
    micro-batch of that bucket regardless of occupancy (short batches
    are zero-padded to the slot count by the engine).
  * **Donated input buffers** — the batch array is built fresh on the
    host every step and never read after the transform, so it is donated
    to the executable (``donate_argnums``) on accelerator platforms and
    XLA may reuse its device buffer for the outputs.  CPU has no buffer
    donation, so the flag is withheld there (jax would warn per call).

``compiles`` / ``hits`` / ``misses`` are exposed for the compile-count
tests and the serve bench: after warmup (one miss per distinct key) the
hit rate across a mixed-bucket workload must be 100%.

The sharded (mesh) route is cached as a plain callable, not an outer
jit: ``kernels/sharded.py`` wraps its collectives in a host-side
watchdog (PR 6), which must stay outside any trace.  Its inner
``shard_map`` program is jit-cached by the kernels layer itself.
"""
from __future__ import annotations

import itertools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import backend as _backend
from repro.kernels import fused3d

Shape = Tuple[int, ...]


class ExecKey(NamedTuple):
    """Everything that selects a distinct compiled transform."""

    bucket: Shape  # (H, W) or (D, H, W)
    batch_slots: int
    scheme: str
    levels: int
    mode: str
    backend: Optional[str]  # None = dispatch default
    mesh_axes: Optional[Tuple[Tuple[str, int], ...]]  # None = single-host


def mesh_signature(mesh: Optional[Any]) -> Optional[Tuple[Tuple[str, int], ...]]:
    """A hashable identity for a mesh: its (axis, size) layout."""
    if mesh is None:
        return None
    return tuple((str(k), int(v)) for k, v in dict(mesh.shape).items())


def plan_label(plans) -> str:
    """Per-level paths as runs, finest first:
    ``("slab-pallas",) * 3 + ("whole-pallas",) * 2`` ->
    ``"slab-pallas×3,whole-pallas×2"``."""
    return ",".join(f"{p}×{len(list(g))}" for p, g in itertools.groupby(plans))


class TransformExecutor:
    """One compiled forward-transform executable per :class:`ExecKey`."""

    def __init__(self):
        self._cache: Dict[ExecKey, Callable] = {}
        # a volume bucket's per-level transform paths (plan_label), worked
        # out once when its executable is built
        self._plans: Dict[ExecKey, str] = {}
        self._traces = 0  # times a cached executable's Python body ran
        self.hits = 0
        self.misses = 0

    @property
    def compiles(self) -> int:
        """Distinct executables built (== cache misses)."""
        return self.misses

    @property
    def traces(self) -> int:
        """Times jax retraced a cached executable's Python body.

        Equal to :attr:`compiles` when the cache works: under jit the
        body runs only while tracing, so a count above ``misses`` means
        an executable recompiled behind the cache's back.
        """
        return self._traces

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return 1.0 if total == 0 else self.hits / total

    # -- building -----------------------------------------------------------

    def _build(self, key: ExecKey, mesh: Optional[Any]) -> Callable:
        from repro import kernels as K

        if key.mesh_axes is not None:
            # host-side watchdog wraps the collectives: cache the
            # callable itself, never an outer jit around it
            def sharded_fn(batch, _mesh=mesh, _key=key):
                self._traces += 1
                return K.dwt_fwd_2d_sharded(
                    batch, _mesh, levels=_key.levels, mode=_key.mode,
                    scheme=_key.scheme,
                )

            return sharded_fn

        if len(key.bucket) == 3:
            # its own name, so a device trace tells the volume's program
            # (``jit_transform_3d``) from the 2-D one (``jit_transform``)
            def transform_3d(batch, _key=key):
                self._traces += 1
                return K.dwt_fwd_nd(
                    batch, levels=_key.levels, mode=_key.mode,
                    backend=_key.backend, scheme=_key.scheme, ndim=3,
                )

            transform = transform_3d
            self._plans[key] = plan_label(
                fused3d.plan_3d_levels(
                    key.bucket, key.levels, key.backend, key.scheme
                )
            )
        else:
            def transform(batch, _key=key):
                self._traces += 1
                return K.dwt_fwd_2d_multi(
                    batch, levels=_key.levels, mode=_key.mode,
                    backend=_key.backend, scheme=_key.scheme,
                )

        # the engine rebuilds the batch host-side every step, so its
        # device buffer is dead after the call: donate it where the
        # platform supports donation (CPU does not and would warn)
        donate = () if _backend.platform() == "cpu" else (0,)
        # compiled ahead of time from the static batch shape, so a
        # lowering or compile failure raises here, at build time — the
        # engine never mistakes one for a transient fault of a run
        batch = jax.ShapeDtypeStruct((key.batch_slots,) + key.bucket, jnp.int32)
        return jax.jit(transform, donate_argnums=donate).lower(batch).compile()

    def executable(self, key: ExecKey, mesh: Optional[Any] = None) -> Callable:
        """The cached executable for ``key`` (built and compiled on first
        use; compile errors propagate from here)."""
        fn = self._cache.get(key)
        if fn is None:
            self.misses += 1
            obs.counter("serve.executor_cache", outcome="miss").inc()
            fn = self._build(key, mesh)
            self._cache[key] = fn
        else:
            self.hits += 1
            obs.counter("serve.executor_cache", outcome="hit").inc()
        return fn

    def run(self, fn: Callable, batch, key: ExecKey):
        """Run ``batch`` through an executable :meth:`executable` returned.

        The ``serve.transform`` span is the transform's dispatch time on
        the host: the call returns before the device finishes, and no
        sync is added, so the device's part shows up wherever the caller
        first blocks on the result.  A volume bucket's span also carries
        ``plan``, its per-level transform paths, and each run counts one
        ``serve.transform_plan{plan=...}``.
        """
        attrs = {"bucket": "x".join(str(s) for s in key.bucket)}
        plan = self._plans.get(key)
        if plan is not None:
            attrs["plan"] = plan
            obs.counter("serve.transform_plan", plan=plan).inc()
        with obs.span("serve.transform", subsystem="serve", **attrs):
            return fn(batch)

    def warmup(self, keys, mesh: Optional[Any] = None) -> int:
        """Build and compile executables for ``keys``; returns how many
        were new.

        Every compile is paid here, before traffic, so it counts as
        set-up time and the first request of a bucket runs a compiled
        program.  The sharded route's callable compiles its inner jit on
        its first run, so warmup runs it once on a zero batch.
        """
        new = 0
        for key in keys:
            if key in self._cache:
                continue
            fn = self.executable(key, mesh)
            if key.mesh_axes is not None:
                zeros = jnp.zeros((key.batch_slots,) + key.bucket, jnp.int32)
                jax.block_until_ready(fn(zeros))
            new += 1
        return new
