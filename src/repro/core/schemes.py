"""Lifting-scheme registry: multiplierless second-generation wavelets.

The paper's (5,3) pair is ONE instance of the lifting scheme; this module
is the abstraction the whole transform stack is parameterized over.  A
scheme is an ordered sequence of :class:`LiftStep`, each a multiplierless
shift-add update of one polyphase stream from the other:

    predict:  odd[n]  += sign * ((sum_i w_i * even[n + o_i] + r) >> k)
    update:   even[n] += sign * ((sum_i w_i * odd[n + o_i]  + r) >> k)

Every step is an integer add/sub plus an arithmetic right shift (the
paper's hardware primitive set); tap weights are realized as signed
power-of-two sums (:func:`wmul`), so no scheme in the registry lowers to
a multiply.  Because a step modifies one stream purely from the OTHER,
its structural inverse is the same read with the sign flipped — every
registered scheme is losslessly invertible by construction, for any
signal length >= 2 and any rounding rule.

Boundary policy (shared by every scheme and engine): each stream entry
corresponds to an original sample position (even entry p -> 2p, odd
entry p -> 2p+1); out-of-range reads reflect the POSITION whole-point
about 0 and N-1 and read the resulting entry of the same stream.  For
the (5,3) this reproduces exactly the seed's d[-1] := d[0] / even-next /
odd-length rules (they were always whole-point reflection in disguise).

Derived structure (computed, never hand-coded):

  * ``fwd_margin`` / ``inv_margin`` — the per-side support, in polyphase
    pairs, a windowed (tiled / halo-exchange) execution needs so its
    interior math reproduces the reference core: found by simulating
    valid-range shrinkage of the step cascade.
  * ``halo`` — ``2 * fwd_margin`` samples: the reflect-halo width of the
    tiled 2D windows and the row count each ``shard_map`` neighbor
    exchange carries.  The seed's hard-coded 2 is just cdf53's value.
  * ``symmetric`` — True when every step's taps mirror around the
    half-sample target position; exactly then whole-point reflection of
    the *input* commutes with the lifting cascade, which is what lets
    windowed engines reflect-pad raw samples instead of band values.

Three pairs of execution primitives implement every engine in the repo:

  :func:`lift_fwd_axis` / :func:`lift_inv_axis`
      band-policy reference math along one axis of a full array — the
      oracle (``core.lifting``), the XLA backend and the sharded row
      stage run this.
  :func:`lift_fwd_axis_ext` / :func:`lift_inv_axis_ext`
      interior-only math along one axis of an already-extended array —
      the sharded column stage (halo rows exchanged via ``ppermute``).
  :func:`lift_fwd_split` / :func:`lift_inv_split`
      either kind of math, per axis, on pre-split polyphase components
      — the body of every Pallas kernel (Mosaic cannot lay out the
      in-kernel even/odd split; :func:`polyphase_split` /
      :func:`polyphase_merge` run it in XLA around the kernel).
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_MODES = ("paper", "jpeg2000")


def check_mode(mode: str) -> None:
    if mode not in _MODES:
        raise ValueError(f"mode must be one of {_MODES}, got {mode!r}")


class LiftStep(NamedTuple):
    """One lifting step: target stream += sign * ((taps + round) >> shift).

    ``kind``   — "predict" (modifies the odd stream from the even) or
                 "update" (modifies the even stream from the odd).
    ``taps``   — ((offset, weight), ...) reads into the OTHER stream,
                 offsets relative to the target index n.
    ``shift``  — arithmetic right-shift amount (floor division by 2^k).
    ``sign``   — +1 or -1 applied to the shifted sum.
    ``round_add`` — constant added before the shift (rounding offset).
    """

    kind: str
    taps: Tuple[Tuple[int, int], ...]
    shift: int
    sign: int
    round_add: int = 0


class LiftingScheme(NamedTuple):
    """A named, registered lifting scheme (see module docstring)."""

    name: str
    steps: Tuple[LiftStep, ...]
    doc: str = ""

    # ---- derived structure (cached per scheme via module-level helpers) --

    @property
    def fwd_margin(self) -> int:
        return _margins(self.steps)

    @property
    def inv_margin(self) -> int:
        return _margins(_inverse_steps(self.steps))

    @property
    def halo(self) -> int:
        """Reflect-halo width in SAMPLES per side for windowed forwards."""
        return 2 * self.fwd_margin

    @property
    def symmetric(self) -> bool:
        return _symmetric(self.steps)

    def can_window(self, n: int) -> bool:
        """True when a windowed (reflect-extended interior) execution
        along a length-``n`` axis reproduces the band-policy reference:
        either the steps commute with whole-point reflection (windows
        gather through :func:`reflect_indices`, so any ``n >= 2`` works
        — deep reflection is still the symmetric extension), or the
        scheme reads no out-of-range entries at all on this length
        (halo 0 and even ``n``, e.g. haar)."""
        if n < 2:
            return False
        if self.symmetric:
            return True
        return self.halo == 0 and n % 2 == 0

    def pair_op_counts(self) -> Dict[str, int]:
        """Adders/shifters per output (s, d) pair — the Table-2 ledger."""
        adds = shifts = 0
        for st in self.steps:
            for _, w in st.taps:
                ta, ts = _wmul_ops(abs(w))
                adds, shifts = adds + ta, shifts + ts
            adds += len(st.taps) - 1  # summing the taps
            if st.round_add:
                adds += 1
            if st.shift:
                shifts += 1
            adds += 1  # fold into the target stream
        return {"adders": adds, "shifters": shifts, "multipliers": 0}


def _inverse_steps(steps: Tuple[LiftStep, ...]) -> Tuple[LiftStep, ...]:
    return tuple(st._replace(sign=-st.sign) for st in reversed(steps))


@functools.lru_cache(maxsize=None)
def _margins(steps: Tuple[LiftStep, ...]) -> int:
    """Smallest per-side pair margin whose interior cascade covers the core.

    Simulates valid-range shrinkage: with both streams valid on
    ``[-m, P+m)`` pairs, each step's target becomes valid only where all
    its reads are; the margin is minimal such that both cores ``[0, P)``
    stay valid after every step.  P drops out of the algebra, so a
    symbolic big-P simulation is exact.
    """
    big = 1 << 20  # stands in for P: margins are tiny by comparison
    for m in range(0, 65):
        lo = {"even": -m, "odd": -m}
        hi = {"even": big + m, "odd": big + m}
        ok = True
        for st in steps:
            tgt, src = _roles(st)
            offs = [o for o, _ in st.taps]
            lo[tgt] = max(lo[tgt], lo[src] - min(offs))
            hi[tgt] = min(hi[tgt], hi[src] - max(offs))
            if lo[tgt] > 0 or hi[tgt] < big:
                ok = False
        if ok:
            return m
    raise ValueError("scheme support too wide (margin > 64 pairs)")


def _symmetric(steps: Tuple[LiftStep, ...]) -> bool:
    """True when every step's taps mirror around the target half-sample.

    A predict step targets sample 2n+1 and reads samples 2(n+o): its taps
    must pair off as o <-> 1-o with equal weights.  An update step
    targets 2n reading 2(n+o)+1: o <-> -1-o.  Exactly these steps
    commute with whole-point reflection of the raw signal, which is the
    identity the windowed engines rest on.
    """
    for st in steps:
        pivot = 1 if st.kind == "predict" else -1
        taps = dict(st.taps)
        if len(taps) != len(st.taps):
            return False
        for o, w in st.taps:
            if taps.get(pivot - o) != w:
                return False
    return True


def _roles(st: LiftStep) -> Tuple[str, str]:
    if st.kind == "predict":
        return "odd", "even"
    if st.kind == "update":
        return "even", "odd"
    raise ValueError(f"unknown step kind {st.kind!r}")


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, LiftingScheme] = {}


def register_scheme(scheme: LiftingScheme) -> LiftingScheme:
    for st in scheme.steps:
        _roles(st)  # validates kind
        if st.sign not in (-1, 1):
            raise ValueError(f"step sign must be +-1, got {st.sign}")
        if st.shift < 0 or not st.taps:
            raise ValueError(f"malformed step in scheme {scheme.name!r}")
    _REGISTRY[scheme.name] = scheme
    return scheme


def available_schemes() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_scheme(scheme) -> LiftingScheme:
    """Resolve a scheme name (or pass a LiftingScheme through)."""
    if isinstance(scheme, LiftingScheme):
        return scheme
    try:
        return _REGISTRY[scheme]
    except KeyError:
        raise ValueError(
            f"unknown lifting scheme {scheme!r}; registered: "
            f"{available_schemes()}"
        ) from None


def resolved_steps(scheme, mode: str) -> Tuple[LiftStep, ...]:
    """The scheme's steps with the mode's rounding rule applied.

    ``jpeg2000`` adds the round-to-nearest offset 2^(shift-1) to every
    UPDATE step (the ITU-T T.800 reversible convention; for cdf53 this
    is exactly the seed's +2).  ``paper`` keeps the declared offsets.

    Keyed on the resolved :class:`LiftingScheme` VALUE, not its name:
    unregistered pass-through instances work, a name collision can never
    serve another scheme's steps, and re-registering a name invalidates
    nothing (the new object is its own cache key).
    """
    return _resolved_steps(get_scheme(scheme), mode)


@functools.lru_cache(maxsize=None)
def _resolved_steps(sch: LiftingScheme, mode: str) -> Tuple[LiftStep, ...]:
    check_mode(mode)
    steps = sch.steps
    if mode == "jpeg2000":
        steps = tuple(
            st._replace(round_add=st.round_add + (1 << (st.shift - 1)))
            if st.kind == "update" and st.shift > 0
            else st
            for st in steps
        )
    return steps


# The paper's (5,3): eq. (5) predict, eq. (7) update.
CDF53 = register_scheme(
    LiftingScheme(
        name="cdf53",
        steps=(
            LiftStep("predict", ((0, 1), (1, 1)), shift=1, sign=-1),
            LiftStep("update", ((-1, 1), (0, 1)), shift=2, sign=+1),
        ),
        doc="LeGall/CDF (5,3) — the paper's worked example (eqs. 5-10)",
    )
)

# Haar / S-transform: the shortest integer wavelet, support one pair.
HAAR = register_scheme(
    LiftingScheme(
        name="haar",
        steps=(
            LiftStep("predict", ((0, 1),), shift=0, sign=-1),
            LiftStep("update", ((0, 1),), shift=1, sign=+1),
        ),
        doc="Haar / S-transform: d = odd - even, s = even + (d >> 1)",
    )
)

# 2/6-style (S+P family): Haar followed by a gradient predict on the
# detail stream from the smooth neighbors — 2-tap low-pass, 6-tap
# high-pass.  The gradient step is antisymmetric, so this scheme is the
# registry's exercise of the non-`symmetric` engine paths.
CDF22 = register_scheme(
    LiftingScheme(
        name="cdf22",
        steps=(
            LiftStep("predict", ((0, 1),), shift=0, sign=-1),
            LiftStep("update", ((0, 1),), shift=1, sign=+1),
            LiftStep("predict", ((1, 1), (-1, -1)), shift=2, sign=+1, round_add=2),
        ),
        doc="2/6 (S+P style): Haar + antisymmetric gradient lift on d",
    )
)

# Multiplierless integer approximation of the CDF 9/7 (the '9/7-M'
# family): four symmetric lifting steps with dyadic weights
# alpha ~ -3/2, beta ~ -1/16, gamma ~ 7/8, delta ~ 7/16.
W97M = register_scheme(
    LiftingScheme(
        name="97m",
        steps=(
            LiftStep("predict", ((0, 3), (1, 3)), shift=1, sign=-1),
            LiftStep("update", ((-1, 1), (0, 1)), shift=4, sign=-1),
            LiftStep("predict", ((0, 7), (1, 7)), shift=3, sign=+1),
            LiftStep("update", ((-1, 7), (0, 7)), shift=4, sign=+1),
        ),
        doc="integer 9/7-M: dyadic shift-add approximation of CDF 9/7",
    )
)


# ---------------------------------------------------------------------------
# Multiplierless weight application (signed power-of-two decomposition).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _naf(w: int) -> Tuple[int, ...]:
    """Non-adjacent-form signed digits of ``w`` as +-2^k terms."""
    terms: List[int] = []
    k = 0
    while w:
        if w & 1:
            d = 2 - (w & 3)  # +1 if w % 4 == 1 else -1
            terms.append(d << k if d > 0 else -(1 << k))
            w -= d
        w >>= 1
        k += 1
    return tuple(terms)


def _wmul_ops(w: int) -> Tuple[int, int]:
    """(extra adds, extra shifts) to form w*x from x with shifts/adds."""
    terms = _naf(w)
    shifts = sum(1 for t in terms if abs(t) > 1)
    return len(terms) - 1, shifts


def wmul(x: Array, w: int) -> Array:
    """w * x as a sum of arithmetic shifts — never a multiply."""
    if w == 0:
        return jnp.zeros_like(x)
    neg = w < 0
    acc = None
    for t in _naf(abs(w)):
        k = abs(t).bit_length() - 1
        term = jnp.left_shift(x, k) if k else x
        if acc is None:
            acc = term if t > 0 else -term
        else:
            acc = acc + term if t > 0 else acc - term
    return -acc if neg else acc


# ---------------------------------------------------------------------------
# Shared slicing helpers.
# ---------------------------------------------------------------------------


def _slc(x: Array, start: int, stop: int, axis: int, stride: int = 1) -> Array:
    return jax.lax.slice_in_dim(x, start, stop, stride=stride, axis=axis)


def split_axis(x: Array, axis: int) -> Tuple[Array, Array]:
    """Even/odd polyphase split along ``axis`` (the lazy wavelet).

    Even lengths reshape to (..., n/2, 2, ...) + contiguous index — pure
    layout ops the SPMD partitioner keeps sharded; odd lengths (rare,
    small) fall back to strided slices.
    """
    axis = axis % x.ndim
    n = x.shape[axis]
    if n % 2 == 0:
        shape = x.shape[:axis] + (n // 2, 2) + x.shape[axis + 1 :]
        pairs = x.reshape(shape)
        return (
            jax.lax.index_in_dim(pairs, 0, axis=axis + 1, keepdims=False),
            jax.lax.index_in_dim(pairs, 1, axis=axis + 1, keepdims=False),
        )
    return _slc(x, 0, n, axis, stride=2), _slc(x, 1, n, axis, stride=2)


def merge_axis(even: Array, odd: Array, axis: int, n: int) -> Array:
    """Interleave the polyphase streams back into ``n`` samples.

    stack+reshape, no scatter (a scatter on a sharded axis makes the
    SPMD partitioner all-gather the whole tensor — measured in the
    pod-sync dry-run).
    """
    axis = axis % even.ndim
    n_o = odd.shape[axis]
    core = jnp.stack([_slc(even, 0, n_o, axis), odd], axis=axis + 1)
    core = core.reshape(
        even.shape[:axis] + (2 * n_o,) + even.shape[axis + 1 :]
    )
    if n > 2 * n_o:  # odd length: the final lone even sample
        n_e = even.shape[axis]
        core = jnp.concatenate(
            [core, _slc(even, n_e - 1, n_e, axis)], axis=axis
        )
    return core


def reflect_indices(start: int, count: int, n: int) -> np.ndarray:
    """Whole-point reflected SAMPLE indices ``start .. start+count-1``.

    Vectorized trace-time map of out-of-range positions into ``[0, n)``
    by reflection about 0 and n-1 (period ``2*(n-1)``).  The windowed
    engines gather their halo'd windows through these maps, so every
    window entry is an exact extension value — no edge-pad junk to
    reason about.
    """
    pos = np.arange(start, start + count)
    if n == 1:
        return np.zeros_like(pos)
    period = 2 * (n - 1)
    q = np.mod(pos, period)
    return np.where(q > n - 1, period - q, q)


def reflect_entries(start: int, count: int, parity: int, n: int) -> np.ndarray:
    """Whole-point reflected BAND-ENTRY indices (see :func:`reflect_entry`),
    vectorized: entry p of the parity-``parity`` stream of a length-``n``
    signal maps to the in-range entry of the same stream."""
    pos = reflect_indices(2 * start + parity, 2 * count, n)[::2]
    if np.any((pos - parity) % 2):
        raise AssertionError("whole-point reflection changed parity")
    return (pos - parity) // 2


def reflect_entry(p: int, parity: int, n: int) -> int:
    """Whole-point position reflection of stream entry ``p`` into range.

    Entry ``p`` of the parity-``parity`` stream of a length-``n`` signal
    sits at sample ``2p + parity``; reflect that position about 0 and
    n-1 until it lands in range, and return the entry (same stream —
    whole-point reflection preserves parity) it maps to.
    """
    pos = 2 * p + parity
    if n == 1:
        return 0
    period = 2 * (n - 1)
    pos %= period
    if pos > n - 1:
        pos = period - pos
    if (pos - parity) % 2:
        raise AssertionError("whole-point reflection changed parity")
    return (pos - parity) // 2


def _policy_read(
    src: Array, parity: int, start: int, count: int, axis: int, n: int
) -> Array:
    """Entries ``src[start : start+count]`` under the reflect policy.

    Out-of-range entries become single-entry slices of the reflected
    in-range entry — slice+concat only, no gathers.
    """
    axis = axis % src.ndim
    src_len = src.shape[axis]
    parts: List[Array] = []
    p = start
    while p < min(0, start + count):
        q = reflect_entry(p, parity, n)
        parts.append(_slc(src, q, q + 1, axis))
        p += 1
    core_hi = min(start + count, src_len)
    if p < core_hi:
        parts.append(_slc(src, p, core_hi, axis))
        p = core_hi
    while p < start + count:
        q = reflect_entry(p, parity, n)
        parts.append(_slc(src, q, q + 1, axis))
        p += 1
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(parts, axis=axis)


def _apply_taps(
    st: LiftStep,
    tgt: Array,
    reads: List[Array],
    inverse: bool,
) -> Array:
    """target +- ((sum of weighted reads + round) >> shift)."""
    acc = None
    for (off, w), col in zip(st.taps, reads):
        term = wmul(col, w)
        acc = term if acc is None else acc + term
    if st.round_add:
        acc = acc + st.round_add
    if st.shift:
        acc = jnp.right_shift(acc, st.shift)
    sign = -st.sign if inverse else st.sign
    return tgt + acc if sign > 0 else tgt - acc


# ---------------------------------------------------------------------------
# The two cascade walkers.  Every engine path is one of these, run
# forward (declared step order) or inverse (reversed order, flipped
# signs) — so the range algebra and the policy reads live exactly once.
# ---------------------------------------------------------------------------


def _walk_policy(
    even: Array, odd: Array, steps, axis: int, n: int, inverse: bool
) -> Tuple[Array, Array]:
    """Run the cascade over full streams with band-policy reads."""
    streams = {"even": even, "odd": odd}
    parity = {"even": 0, "odd": 1}
    for st in reversed(steps) if inverse else steps:
        tgt, src = _roles(st)
        t = streams[tgt]
        t_len = t.shape[axis]
        reads = [
            _policy_read(streams[src], parity[src], off, t_len, axis, n)
            for off, _ in st.taps
        ]
        streams[tgt] = _apply_taps(st, t, reads, inverse=inverse)
    return streams["even"], streams["odd"]


def _walk_ext(
    even: Array, odd: Array, steps, axis: int, margin: int, inverse: bool
) -> Tuple[Array, Array]:
    """Run the cascade as interior-only math on margin-extended streams.

    Tracks each stream's valid range [lo, hi) and array start offset in
    extended-pair coordinates: a step's target becomes valid only where
    all its reads are, and the final cores are cropped to
    ``[margin, margin + core)``.
    """
    p_ext = even.shape[axis]
    core = p_ext - 2 * margin
    arrs = {"even": even, "odd": odd}
    lo = {"even": 0, "odd": 0}
    hi = {"even": p_ext, "odd": p_ext}
    start = {"even": 0, "odd": 0}
    for st in reversed(steps) if inverse else steps:
        tgt, src = _roles(st)
        offs = [o for o, _ in st.taps]
        new_lo = max(lo[tgt], lo[src] - min(offs))
        new_hi = min(hi[tgt], hi[src] - max(offs))
        reads = [
            _slc(arrs[src], new_lo + off - start[src], new_hi + off - start[src], axis)
            for off, _ in st.taps
        ]
        t = _slc(arrs[tgt], new_lo - start[tgt], new_hi - start[tgt], axis)
        arrs[tgt] = _apply_taps(st, t, reads, inverse=inverse)
        lo[tgt], hi[tgt], start[tgt] = new_lo, new_hi, new_lo
    return (
        _slc(arrs["even"], margin - start["even"], margin + core - start["even"], axis),
        _slc(arrs["odd"], margin - start["odd"], margin + core - start["odd"], axis),
    )


# ---------------------------------------------------------------------------
# Band-policy transforms (the reference semantics, any scheme, any N >= 2).
# ---------------------------------------------------------------------------


def lift_fwd_axis(
    x: Array, scheme, axis: int = -1, mode: str = "paper"
) -> Tuple[Array, Array]:
    """One forward level along ``axis`` under the band reflect policy."""
    sch = get_scheme(scheme)
    axis = axis % x.ndim
    n = x.shape[axis]
    if n < 2:
        raise ValueError(f"need at least 2 samples, got {n}")
    even, odd = split_axis(x, axis)
    return _walk_policy(
        even, odd, resolved_steps(sch, mode), axis, n, inverse=False
    )


def lift_inv_axis(
    s: Array, d: Array, scheme, axis: int = -1, mode: str = "paper"
) -> Array:
    """Structural inverse of :func:`lift_fwd_axis` (reversed steps)."""
    sch = get_scheme(scheme)
    axis = axis % s.ndim
    n_e, n_o = s.shape[axis], d.shape[axis]
    if n_e - n_o not in (0, 1):
        raise ValueError(f"band length mismatch: s={n_e}, d={n_o}")
    n = n_e + n_o
    even, odd = _walk_policy(
        s, d, resolved_steps(sch, mode), axis, n, inverse=True
    )
    return merge_axis(even, odd, axis, n)


# ---------------------------------------------------------------------------
# Interior transforms on extended arrays (windowed/tiled/sharded engines).
# ---------------------------------------------------------------------------


def lift_fwd_axis_ext(
    x: Array, scheme, axis: int = -1, mode: str = "paper"
) -> Tuple[Array, Array]:
    """One forward level along ``axis`` of a halo-extended array.

    ``x`` carries ``scheme.halo`` extension samples at BOTH ends of the
    axis (even total length).  Interior math only — the halo encodes the
    boundary policy — returning the core ``(s, d)`` streams, each
    ``n_ext/2 - 2*fwd_margin`` entries.
    """
    sch = get_scheme(scheme)
    axis = axis % x.ndim
    even, odd = split_axis(x, axis)
    return _walk_ext(
        even, odd, resolved_steps(sch, mode), axis, sch.fwd_margin,
        inverse=False,
    )


def lift_inv_axis_ext(
    s_ext: Array, d_ext: Array, scheme, axis: int = -1, mode: str = "paper"
) -> Array:
    """One inverse level along ``axis`` from margin-extended bands.

    ``s_ext`` / ``d_ext`` carry ``scheme.inv_margin`` extension entries
    at both ends of the axis.  Returns the merged core signal,
    ``2 * (len - 2*inv_margin)`` samples.
    """
    sch = get_scheme(scheme)
    axis = axis % s_ext.ndim
    m = sch.inv_margin
    even, odd = _walk_ext(
        s_ext, d_ext, resolved_steps(sch, mode), axis, m, inverse=True
    )
    return merge_axis(even, odd, axis, 2 * (s_ext.shape[axis] - 2 * m))


# ---------------------------------------------------------------------------
# Pre-split N-D levels: the kernel bodies.  Mosaic cannot lay out the
# minor-axis (n/2, 2) reshape of :func:`split_axis` (nor the stack+reshape
# of :func:`merge_axis`), so the Pallas engines split and merge polyphase
# components in their XLA wrappers and run only the cascades in-kernel.
# Components and bands share one code order: bit j of the code means odd
# samples (forward input) / highpass (band) along axis -(j+1) — the
# ``core.lifting`` band code — so the forward cascade turns component c
# into band c in place.
# ---------------------------------------------------------------------------


def polyphase_split(x: Array, ndim: int) -> List[Array]:
    """The ``2**ndim`` polyphase components of ``x`` over its last
    ``ndim`` axes, in code order (bit j = odd samples along axis -(j+1))."""
    comps = [x]
    for j in range(ndim):
        nxt: List[Array] = [None] * (2 * len(comps))  # type: ignore[list-item]
        for c, a in enumerate(comps):
            nxt[c], nxt[c | (1 << j)] = split_axis(a, a.ndim - 1 - j)
        comps = nxt
    return comps


def polyphase_merge(comps: List[Array], shape: Tuple[int, ...]) -> Array:
    """Inverse of :func:`polyphase_split`; ``shape`` is the trailing
    sample shape (one entry per split axis)."""
    ndim = len(shape)
    cur = list(comps)
    for j in reversed(range(ndim)):
        half = 1 << j
        cur = [
            merge_axis(cur[c], cur[c | half], cur[c].ndim - 1 - j, shape[-(j + 1)])
            for c in range(half)
        ]
    return cur[0]


def lift_fwd_split(
    comps: List[Array], scheme, mode: str, lengths: Tuple[Optional[int], ...]
) -> List[Array]:
    """One forward N-D level on pre-split polyphase components.

    ``lengths[j]`` describes axis -(j+1): the signal's sample count there
    for band-policy reads (:func:`lift_fwd_axis` semantics), or ``None``
    for interior math on streams extended by ``fwd_margin`` entries per
    side (:func:`lift_fwd_axis_ext` semantics).  Axes run -1 first, the
    reference composition order.  Returns the bands in code order.
    """
    sch = get_scheme(scheme)
    steps = resolved_steps(sch, mode)
    out = list(comps)
    for j, n in enumerate(lengths):
        bit = 1 << j
        for c in range(len(out)):
            if c & bit:
                continue
            e, o = out[c], out[c | bit]
            axis = e.ndim - 1 - j
            if n is None:
                out[c], out[c | bit] = _walk_ext(
                    e, o, steps, axis, sch.fwd_margin, inverse=False
                )
            else:
                out[c], out[c | bit] = _walk_policy(
                    e, o, steps, axis, n, inverse=False
                )
    return out


def lift_inv_split(
    bands: List[Array], scheme, mode: str, lengths: Tuple[Optional[int], ...]
) -> List[Array]:
    """Inverse of :func:`lift_fwd_split`: code-ordered bands (extended by
    ``inv_margin`` entries per side along ``None`` axes) -> the polyphase
    components of the core signal, in code order."""
    sch = get_scheme(scheme)
    steps = resolved_steps(sch, mode)
    out = list(bands)
    for j in reversed(range(len(lengths))):
        n, bit = lengths[j], 1 << j
        for c in range(len(out)):
            if c & bit:
                continue
            s, d = out[c], out[c | bit]
            axis = s.ndim - 1 - j
            if n is None:
                out[c], out[c | bit] = _walk_ext(
                    s, d, steps, axis, sch.inv_margin, inverse=True
                )
            else:
                out[c], out[c | bit] = _walk_policy(
                    s, d, steps, axis, n, inverse=True
                )
    return out


def extend_band(
    b: Array, parity: int, axis: int, n: int, left: int, right: int
) -> Array:
    """Extend a band by policy entries for the windowed inverse.

    ``n`` is the ORIGINAL signal length along the axis (pre-split);
    entries are appended/prepended via :func:`reflect_entry` — for cdf53
    this reproduces the seed's role policies (s edge / d whole-point /
    odd-length d[n] := d[n-1]) from the one shared rule.
    """
    axis = axis % b.ndim
    blen = b.shape[axis]
    parts: List[Array] = []
    for p in range(-left, 0):
        q = reflect_entry(p, parity, n)
        parts.append(_slc(b, q, q + 1, axis))
    parts.append(b)
    for p in range(blen, blen + right):
        q = reflect_entry(p, parity, n)
        parts.append(_slc(b, q, q + 1, axis))
    if len(parts) == 1:
        return b
    return jnp.concatenate(parts, axis=axis)
