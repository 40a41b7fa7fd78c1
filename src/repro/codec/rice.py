"""Vectorized adaptive Golomb-Rice coding for integer wavelet bands.

The paper's multiplierless DWT is only the front half of a lossless
coder; this module is the back half's arithmetic core.  Signed band
coefficients are zigzag-mapped to unsigned magnitudes and Rice-coded in
independent blocks of ``BLOCK_VALUES`` samples:

  * one Rice parameter ``k`` per block, chosen ON DEVICE by an exhaustive
    shift-add cost scan (for every candidate ``k`` the exact total code
    length is a sum of ``min(u >> k, ...)`` terms — integer shifts,
    compares and adds only, in the spirit of the paper's multiplierless
    modules; the argmin is the optimal ``k``, not a heuristic);
  * each value codes as ``q = u >> k`` unary ones, a zero terminator,
    then the ``k`` remainder bits; quotients at or above ``Q_MAX``
    escape to ``Q_MAX`` ones followed by the raw 32-bit value, which
    bounds every code at ``LMAX`` bits (outlier-proof, including the
    zigzag of INT32_MIN);
  * packing works per value, never per bit: a code is at most two
    pieces of at most 32 bits (an escape's ``Q_MAX`` ones, then the low
    32 bits of the code), and each piece lands in the word its bit
    offset names and, where it crosses a boundary, the next one.
    :func:`pack_words` runs on the path the ``kernels/backend.py``
    policy resolves: a Pallas kernel (TPU, or explicit request) that
    shift-ors the pieces into words with blocks on lanes, or on the XLA
    fallback a segment sum of the pieces over their word indices.  All
    paths are bit-identical.

Blocks are byte-aligned and self-contained (own ``k``, own byte length),
so decode parallelizes ACROSS blocks, every block in lockstep over its
``BLOCK_VALUES`` values.  :func:`unpack` runs on the same policy: a
Pallas kernel with blocks on lanes in which each lane reads its codes
from a window of three stream words (leading ones, shifts and selects;
no per-bit state), or on the XLA fallback a ``lax.scan`` over each
block's bit grid that resolves every unary run in O(1) through a
next-zero suffix scan.  All paths give the same values.

Host-facing entry points (``encode_band`` / ``decode_band``) take and
return numpy arrays and chunk internally (``CHUNK_BLOCKS`` blocks per
compiled dispatch, padded to power-of-two buckets) so gigabyte bands
never materialize on the device at once and the jit cache stays
bounded.  Each records ONE span per band (``codec.encode_band`` /
``codec.decode_band``) around its chunk loop, with the loop's
``chunks``, ``blocks`` and ``wait_s`` (seconds blocked on the device)
as attributes — never a span per chunk.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import backend as B

# block geometry: 256 samples per Rice block keeps the k-table overhead
# under 0.2 bits/value while the per-block cost scan stays adaptive
BLOCK_VALUES = 256
Q_MAX = 8  # unary quotient cap; q >= Q_MAX escapes to 32 raw bits
K_MAX = 24  # largest Rice parameter the cost scan considers
LMAX = Q_MAX + 32  # longest code: escape (non-escape max is Q_MAX+K_MAX)

BYTES_CAP = BLOCK_VALUES * LMAX // 8  # worst-case encoded bytes per block
_WORDS = BYTES_CAP // 4  # packed 32-bit words per block (320)
_LANES = 128  # blocks per kernel tile: one vreg row of lanes

# encode/decode dispatch width: blocks per compiled chunk (one kernel lane
# tile; bounds the XLA decoder's per-bit workspace)
CHUNK_BLOCKS = 128


# ---------------------------------------------------------------------------
# Zigzag mapping: signed int32 <-> unsigned magnitude (shift/xor only).
# ---------------------------------------------------------------------------


def zigzag(x: jax.Array) -> jax.Array:
    """Signed int32 -> uint32 with small magnitudes staying small.

    ``(x << 1) ^ (x >> 31)`` — arithmetic shift and xor only.  INT32_MIN
    maps to 0xFFFFFFFF (the escape path carries it losslessly).
    """
    u = jnp.bitwise_xor(jnp.left_shift(x, 1), jnp.right_shift(x, 31))
    return jax.lax.bitcast_convert_type(u, jnp.uint32)


def unzigzag(u: jax.Array) -> jax.Array:
    """Inverse of :func:`zigzag` (uint32 -> int32)."""
    neg = jnp.where(
        (u & jnp.uint32(1)).astype(jnp.bool_),
        jnp.uint32(0xFFFFFFFF),
        jnp.uint32(0),
    )
    x = jnp.bitwise_xor(jnp.right_shift(u, jnp.uint32(1)), neg)
    return jax.lax.bitcast_convert_type(x, jnp.int32)


# ---------------------------------------------------------------------------
# Code -> word packing: the backend-dispatched kernel stage.
# ---------------------------------------------------------------------------


def _split(v: jax.Array, end: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A right-aligned piece ``v`` whose last bit falls at bit ``end`` of
    a two-word window (bit 0 is the first word's MSB; ``end`` in 0..63,
    the piece no longer than ``end`` nor than 32 bits) -> its part in each
    word.  Every shift stays in 0..31: a piece of length 0 is 0."""
    over = end - 32
    head = jnp.where(
        over > 0,
        jax.lax.shift_right_logical(v, jnp.clip(over, 0, 31)),
        jnp.left_shift(v, jnp.clip(-over, 0, 31)),
    )
    tail = jnp.where(over > 0, jnp.left_shift(v, jnp.clip(32 - over, 0, 31)), 0)
    return head, tail


def _pieces(lo: jax.Array, lens: jax.Array):
    """A code of ``lens`` bits whose low 32 are ``lo`` -> two pieces of at
    most 32 bits: the ``lens - 32`` leading ones of an escape (length 0
    for any other code), then ``lo``."""
    hl = jnp.maximum(lens - 32, 0)
    return (jnp.left_shift(1, hl) - 1, hl), (lo, lens - hl)


def _put(state, v, ln):
    """Append one piece to each lane's partial word.  Returns the new
    state and the word the piece completed, with its row (-1: none)."""
    acc, fill, widx = state
    end = fill + ln
    head, tail = _split(v, end)
    word = jnp.bitwise_or(acc, head)
    full = end >= 32
    row = jnp.where(full, widx, -1)
    state = (
        jnp.where(full, tail, word),
        jnp.where(full, end - 32, end),
        widx + full.astype(jnp.int32),
    )
    return state, row, word


def _pack_kernel(lo_ref, lens_ref, words_ref):
    """Shift-or pack of one lane tile: blocks on lanes, values and words
    on rows.  Each lane carries its partial word, fill and word index
    through its values in order.  The words that a tile of eight values
    completes land in their rows by a select over the row groups, since
    every lane writes rows of its own."""
    groups, sub, lanes = words_ref.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (sub, lanes), 0)
    words_ref[...] = jnp.zeros(words_ref.shape, jnp.int32)

    def write(done):
        def group(j, _):
            blk = words_ref[j]
            for row, word in done:
                blk = jnp.where(rows == row - sub * j, word, blk)
            words_ref[j] = blk

        jax.lax.fori_loop(0, groups, group, None)

    def step(g, state):
        lo, lens = lo_ref[g], lens_ref[g]
        done = []
        for r in range(sub):
            for v, ln in _pieces(lo[r : r + 1], lens[r : r + 1]):
                state, row, word = _put(state, v, ln)
                done.append((row, word))
        write(done)
        return state

    zero = jnp.zeros((1, lanes), jnp.int32)
    acc, fill, widx = jax.lax.fori_loop(
        0, lo_ref.shape[0], step, (zero, zero, zero)
    )
    write([(jnp.where(fill > 0, widx, -1), acc)])  # the last partial word


def _pack_words_pallas(
    lo: jax.Array, lens: jax.Array, interpret: bool
) -> jax.Array:
    from jax.experimental import pallas as pl

    nb = lo.shape[0]
    lanes = -(-nb // _LANES) * _LANES  # padded lanes carry 0-bit codes

    def lay(a):  # (nb, BLOCK_VALUES) -> (BLOCK_VALUES // 8, 8, lanes)
        a = jnp.pad(a.T, ((0, 0), (0, lanes - nb)))
        return a.reshape(BLOCK_VALUES // 8, 8, lanes)

    spec = pl.BlockSpec((BLOCK_VALUES // 8, 8, _LANES), lambda c: (0, 0, c))
    words = pl.pallas_call(
        _pack_kernel,
        grid=(lanes // _LANES,),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((_WORDS // 8, 8, _LANES), lambda c: (0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((_WORDS // 8, 8, lanes), jnp.int32),
        interpret=interpret,
    )(lay(lo), lay(lens))
    return words.reshape(_WORDS, lanes)[:, :nb].T


def _pack_words_xla(
    lo: jax.Array, lens: jax.Array, offs: jax.Array
) -> jax.Array:
    nb = lo.shape[0]
    base = jnp.arange(nb, dtype=jnp.int32)[:, None] * _WORDS
    idx, val = [], []
    for v, ln in _pieces(lo, lens):
        head, tail = _split(v, (offs & 31) + ln)
        w = base + jnp.right_shift(offs, 5)
        idx += [w, w + 1]
        val += [head, tail]
        offs = offs + ln
    # codes occupy disjoint bits, so the sum of the pieces IS their or;
    # a tail past the chunk's last word is 0 and falls off the end
    words = jax.ops.segment_sum(
        jnp.concatenate([a.reshape(-1) for a in val]),
        jnp.concatenate([a.reshape(-1) for a in idx]),
        num_segments=nb * _WORDS,
    )
    return words.reshape(nb, _WORDS)


def pack_words(
    lo: jax.Array, lens: jax.Array, offs: jax.Array, pack_backend: str
) -> jax.Array:
    """Per-value codes -> (nb, _WORDS) packed int32 words.

    ``lo`` holds the low 32 bits of each code (int32 bit pattern),
    ``lens`` its length in bits and ``offs`` their exclusive prefix sum
    along the block.  Bit ``32w + i`` of a block is bit ``31 - i`` of
    word ``w`` (MSB-first within every byte).  ``pack_backend`` is a
    RESOLVED backend name (``kernels/backend.py``); all three paths
    produce bit-identical words.
    """
    if pack_backend == "xla":
        return _pack_words_xla(lo, lens, offs)
    return _pack_words_pallas(lo, lens, interpret=(pack_backend == "interpret"))


# ---------------------------------------------------------------------------
# Compiled per-chunk encode.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("pack_backend",))
def _encode_chunk(
    xb: jax.Array, *, pack_backend: str
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Encode (nb, BLOCK_VALUES) int32 blocks.

    Returns (bytes (nb, BYTES_CAP) uint8, nbits (nb,) int32, k (nb,)).
    """
    nb = xb.shape[0]
    u = zigzag(xb)

    # exact per-block cost of every candidate k: integer shift/compare/add
    costs = []
    for k in range(K_MAX + 1):
        q = jnp.right_shift(u, jnp.uint32(k))
        esc = q >= jnp.uint32(Q_MAX)
        ln = jnp.where(
            esc,
            jnp.int32(Q_MAX + 32),
            jnp.minimum(q, jnp.uint32(Q_MAX)).astype(jnp.int32) + (1 + k),
        )
        costs.append(jnp.sum(ln, axis=1))
    ks = jnp.argmin(jnp.stack(costs), axis=0).astype(jnp.int32)  # (nb,)

    k_u = ks[:, None].astype(jnp.uint32)
    q = jnp.right_shift(u, k_u)
    esc = q >= jnp.uint32(Q_MAX)
    q_c = jnp.minimum(q, jnp.uint32(Q_MAX)).astype(jnp.int32)
    lens = jnp.where(esc, jnp.int32(Q_MAX + 32), q_c + 1 + ks[:, None])
    offs = jnp.cumsum(lens, axis=1) - lens  # exclusive prefix sum
    nbits = offs[:, -1] + lens[:, -1]
    rem = u & (jnp.left_shift(jnp.uint32(1), k_u) - jnp.uint32(1))

    # the low 32 bits of each code: q ones, the 0, the k remainder bits;
    # an escape's are the raw u after its Q_MAX ones
    ones = jnp.left_shift(jnp.uint32(1), q_c.astype(jnp.uint32)) - jnp.uint32(1)
    code = jnp.left_shift(ones, k_u + jnp.uint32(1)) | rem
    lo = jax.lax.bitcast_convert_type(jnp.where(esc, u, code), jnp.int32)
    words = pack_words(lo, lens, offs, pack_backend)
    by = jnp.stack(
        [(jnp.right_shift(words, s) & 0xFF) for s in (24, 16, 8, 0)], axis=-1
    )
    return by.reshape(nb, BYTES_CAP).astype(jnp.uint8), nbits, ks


# ---------------------------------------------------------------------------
# Word -> value unpacking: the backend-dispatched decode stage.
# ---------------------------------------------------------------------------


def _view(a: jax.Array, b: jax.Array, s: jax.Array) -> jax.Array:
    """The 32 bits that start ``s`` (0..31) bits into the word pair ``a``,
    ``b``.  ``b`` goes in by two shifts that stay in 0..31, so ``s`` 0
    takes none of it."""
    lsr = jax.lax.shift_right_logical
    return jnp.left_shift(a, s) | lsr(lsr(b, 1), 31 - s)


def _unpack_kernel(ks_ref, words_ref, out_ref):
    """Decode one lane tile: blocks on lanes, words and values on rows.

    Each lane carries a three-word window of its stream, the bit offset
    into the first word and the index of the next word through its values
    in order.  A code is at most ``LMAX`` (40) bits, so the window holds
    all of it wherever it starts in the first word, and a value moves the
    window on by at most two words: fetched for every lane by a select
    over the word row groups (a row past the block's words reads 0).
    Value ``i`` lands in output row ``i`` of every lane."""
    groups, sub, lanes = words_ref.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (sub, lanes), 0)
    k = ks_ref[...]
    k_mask = jnp.left_shift(1, k) - 1

    def fold(a):  # the one row of each lane's column that was selected
        return functools.reduce(
            jnp.bitwise_or, [a[r : r + 1] for r in range(sub)]
        )

    def fetch(widx):  # words widx and widx + 1 of each lane
        def group(j, acc):
            blk = words_ref[j]
            d = rows + sub * j - widx
            return (
                jnp.where(d == 0, blk, acc[0]),
                jnp.where(d == 1, blk, acc[1]),
            )

        zero = jnp.zeros((sub, lanes), jnp.int32)
        f0, f1 = jax.lax.fori_loop(0, groups, group, (zero, zero))
        return fold(f0), fold(f1)

    def value(state):
        w0, w1, w2, pos, widx = state
        f0, f1 = fetch(widx)
        head = _view(w0, w1, pos)
        q = jnp.minimum(jax.lax.clz(jnp.bitwise_not(head)), Q_MAX)
        n = q + 1 + k  # length of a code that does not escape: 1..32
        rem = jax.lax.shift_right_logical(head, jnp.maximum(32 - n, 0))
        rem = rem & k_mask
        p = pos + Q_MAX  # an escape's 32 raw bits start here: 8..39
        hi = p >= 32
        raw = _view(jnp.where(hi, w1, w0), jnp.where(hi, w2, w1), p & 31)
        esc = q >= Q_MAX
        u = jnp.where(esc, raw, jnp.left_shift(q, k) | rem)
        pos = pos + jnp.where(esc, LMAX, n)
        s = jnp.right_shift(pos, 5)  # words the window moves on: 0..2
        one, two = s == 1, s == 2
        w0, w1, w2 = (
            jnp.where(one, w1, jnp.where(two, w2, w0)),
            jnp.where(one, w2, jnp.where(two, f0, w1)),
            jnp.where(one, f0, jnp.where(two, f1, w2)),
        )
        return (w0, w1, w2, pos & 31, widx + s), u

    def step(g, state):
        blk = jnp.zeros((sub, lanes), jnp.int32)
        for r in range(sub):
            state, u = value(state)
            blk = jnp.where(rows == r, u, blk)
        out_ref[g] = blk
        return state

    first = words_ref[0]
    zero = jnp.zeros((1, lanes), jnp.int32)
    start = (first[0:1], first[1:2], first[2:3], zero, zero + 3)
    jax.lax.fori_loop(0, out_ref.shape[0], step, start)


def _unpack_pallas(
    words: jax.Array, ks: jax.Array, interpret: bool
) -> jax.Array:
    from jax.experimental import pallas as pl

    nb, nw = words.shape
    lanes = -(-nb // _LANES) * _LANES  # padded lanes decode zero words
    rows = -(-nw // 8) * 8  # at least one row group, whole groups only
    words = jnp.pad(words.T, ((0, rows - nw), (0, lanes - nb)))
    ks = jnp.pad(ks, (0, lanes - nb)).reshape(1, lanes)
    out = (BLOCK_VALUES // 8, 8)
    us = pl.pallas_call(
        _unpack_kernel,
        grid=(lanes // _LANES,),
        in_specs=[
            pl.BlockSpec((1, _LANES), lambda c: (0, c)),
            pl.BlockSpec((rows // 8, 8, _LANES), lambda c: (0, 0, c)),
        ],
        out_specs=pl.BlockSpec((*out, _LANES), lambda c: (0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((*out, lanes), jnp.int32),
        interpret=interpret,
    )(ks, words.reshape(rows // 8, 8, lanes))
    return us.reshape(BLOCK_VALUES, lanes)[:, :nb].T


def _unpack_xla(words: jax.Array, ks: jax.Array) -> jax.Array:
    nb, nw = words.shape
    nbits = nw * 32
    lane = jnp.arange(32, dtype=jnp.uint32)
    bits = (
        jnp.right_shift(words.astype(jnp.uint32)[..., None], 31 - lane) & 1
    ).astype(jnp.int32).reshape(nb, nbits)

    # next-zero-at-or-after: suffix cummin over masked positions resolves
    # every unary run in O(1) per scan step
    pos = jnp.arange(nbits, dtype=jnp.int32)
    idx = jnp.where(bits == 0, pos, nbits)
    nz = jnp.flip(jax.lax.cummin(jnp.flip(idx, axis=-1), axis=1), axis=-1)

    k_u = ks.astype(jnp.uint32)
    m = jnp.arange(K_MAX, dtype=jnp.int32)
    t = jnp.arange(32, dtype=jnp.int32)

    def step(off, _):
        o = jnp.clip(off, 0, nbits - 1)
        nzp = jnp.take_along_axis(nz, o[:, None], axis=1)[:, 0]
        q = jnp.clip(nzp - off, 0, Q_MAX)
        esc = q >= Q_MAX
        # remainder: gather K_MAX bits, keep the first k, weight by shifts
        gi = jnp.clip(off[:, None] + q[:, None] + 1 + m[None, :], 0, nbits - 1)
        rb = jnp.take_along_axis(bits, gi, axis=1).astype(jnp.uint32)
        sh = jnp.clip(ks[:, None] - 1 - m[None, :], 0, 31).astype(jnp.uint32)
        r = jnp.sum(
            jnp.where(m[None, :] < ks[:, None], jnp.left_shift(rb, sh), 0),
            axis=1,
            dtype=jnp.uint32,
        )
        u_rice = jnp.bitwise_or(
            jnp.left_shift(q.astype(jnp.uint32), k_u), r
        )
        # escape: 32 raw bits after the Q_MAX unary prefix
        ge = jnp.clip(off[:, None] + Q_MAX + t[None, :], 0, nbits - 1)
        eb = jnp.take_along_axis(bits, ge, axis=1).astype(jnp.uint32)
        u_esc = jnp.sum(
            jnp.left_shift(eb, (31 - t).astype(jnp.uint32)),
            axis=1,
            dtype=jnp.uint32,
        )
        u = jnp.where(esc, u_esc, u_rice)
        adv = jnp.where(esc, Q_MAX + 32, q + 1 + ks)
        return off + adv, u

    off0 = jnp.zeros((nb,), jnp.int32)
    _, us = jax.lax.scan(step, off0, None, length=BLOCK_VALUES)
    return jnp.swapaxes(us, 0, 1)


def unpack(words: jax.Array, ks: jax.Array, unpack_backend: str) -> jax.Array:
    """(nb, W) packed int32 words with per-block ``k`` -> (nb,
    BLOCK_VALUES) uint32 zigzagged values.

    The inverse of :func:`pack_words`, in the same bit order, for any
    ``W``; the words past a block's stream are 0.  ``unpack_backend`` is
    a RESOLVED backend name: the Pallas kernel, or on the XLA fallback a
    ``lax.scan`` over the block's bit grid that resolves each step's
    unary run through a next-zero suffix scan.  All paths give the same
    values on every valid stream.
    """
    if unpack_backend == "xla":
        return _unpack_xla(words, ks)
    us = _unpack_pallas(words, ks, interpret=(unpack_backend == "interpret"))
    return jax.lax.bitcast_convert_type(us, jnp.uint32)


# ---------------------------------------------------------------------------
# Compiled per-chunk decode.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("unpack_backend",))
def _decode_chunk(
    words: jax.Array, ks: jax.Array, *, unpack_backend: str
) -> jax.Array:
    """Decode (nb, W) rows of big-endian stream words with per-block k ->
    (nb, BLOCK_VALUES) int32."""
    return unzigzag(unpack(words, ks, unpack_backend))


# ---------------------------------------------------------------------------
# Host-facing band API (numpy in/out, internal chunking + shape buckets).
# ---------------------------------------------------------------------------


def _bucket(n: int, cap: Optional[int] = None) -> int:
    """Next power of two >= n (bounds the jit cache per distinct shape)."""
    b = 1 << max(0, (n - 1).bit_length())
    return min(b, cap) if cap is not None else b


def n_blocks(count: int) -> int:
    return -(-count // BLOCK_VALUES)


def _to_host(*arrays: jax.Array) -> list:
    """``arrays`` as numpy; the time blocked on the device goes to the open
    span's ``wait_s``.  The copies are enqueued before the block, so they
    follow the computation on the device as a bare ``np.asarray`` would,
    and the host waits out only what is left of them."""
    for a in arrays:
        a.copy_to_host_async()
    with obs.waiting():
        jax.block_until_ready(arrays)
    return [np.asarray(a) for a in arrays]


def encode_band(
    x: np.ndarray, backend: Optional[str] = None
) -> Tuple[bytes, np.ndarray, np.ndarray]:
    """Rice-encode a flat integer band.

    Returns ``(payload, k_table, byte_lengths)`` — the byte-aligned
    concatenated block bitstreams plus the per-block Rice parameters
    (uint8) and encoded byte counts (uint16) the container serializes.
    ``backend`` selects the pack kernel path (None = policy default).
    """
    flat = np.ascontiguousarray(x).reshape(-1).astype(np.int32)
    count = flat.size
    if count == 0:
        return b"", np.zeros(0, np.uint8), np.zeros(0, np.uint16)
    nb = n_blocks(count)
    pad = nb * BLOCK_VALUES - count
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.int32)])
    blocks = flat.reshape(nb, BLOCK_VALUES)
    resolved = B.resolve_backend(backend)

    ks = np.zeros(nb, np.uint8)
    blens = np.zeros(nb, np.int64)
    parts = []
    with obs.span("codec.encode_band", subsystem="codec",
                  chunks=-(-nb // CHUNK_BLOCKS), blocks=nb, wait_s=0.0):
        for start in range(0, nb, CHUNK_BLOCKS):
            chunk = blocks[start : start + CHUNK_BLOCKS]
            rows = chunk.shape[0]
            bucket = _bucket(rows, CHUNK_BLOCKS)
            if bucket != rows:
                chunk = np.concatenate(
                    [chunk, np.zeros((bucket - rows, BLOCK_VALUES), np.int32)]
                )
            by, nbits, k = _to_host(
                *_encode_chunk(jnp.asarray(chunk), pack_backend=resolved)
            )
            by = by[:rows]
            blen = (nbits[:rows] + 7) // 8
            ks[start : start + rows] = k[:rows].astype(np.uint8)
            blens[start : start + rows] = blen
            mask = np.arange(BYTES_CAP)[None, :] < blen[:, None]
            parts.append(by[mask].tobytes())
    return b"".join(parts), ks, blens.astype(np.uint16)


def decode_band(
    payload: bytes,
    k_table: np.ndarray,
    byte_lengths: np.ndarray,
    count: int,
    backend: Optional[str] = None,
) -> np.ndarray:
    """Inverse of :func:`encode_band` -> flat int32 array of ``count``.
    ``backend`` selects the unpack kernel path (None = policy default)."""
    if count == 0:
        return np.zeros(0, np.int32)
    nb = n_blocks(count)
    ks = np.asarray(k_table, np.int32)
    blens = np.asarray(byte_lengths, np.int64)
    if ks.shape[0] != nb or blens.shape[0] != nb:
        raise ValueError(
            f"rice tables describe {ks.shape[0]} blocks, geometry needs {nb}"
        )
    if int(blens.sum()) != len(payload):
        raise ValueError(
            f"rice payload is {len(payload)} bytes, block lengths sum to "
            f"{int(blens.sum())} (truncated or corrupt stream)"
        )
    raw = np.frombuffer(payload, np.uint8)
    offs = np.concatenate([[0], np.cumsum(blens)])
    out = np.zeros(nb * BLOCK_VALUES, np.int32)
    resolved = B.resolve_backend(backend)
    with obs.span("codec.decode_band", subsystem="codec",
                  chunks=-(-nb // CHUNK_BLOCKS), blocks=nb, wait_s=0.0):
        for start in range(0, nb, CHUNK_BLOCKS):
            rows = min(CHUNK_BLOCKS, nb - start)
            lens_c = blens[start : start + rows]
            maxlen = _bucket(max(int(lens_c.max()), 8))
            bucket = _bucket(rows, CHUNK_BLOCKS)
            mat = np.zeros((bucket, maxlen), np.uint8)
            mask = np.arange(maxlen)[None, :] < lens_c[:, None]
            mat[:rows][mask] = raw[offs[start] : offs[start + rows]]
            kc = np.zeros(bucket, np.int32)
            kc[:rows] = ks[start : start + rows]
            # big-endian words of the zero-padded rows (maxlen is a power
            # of two >= 8, so whole words)
            words = mat.view(">u4").astype(np.uint32).view(np.int32)
            (dec,) = _to_host(
                _decode_chunk(
                    jnp.asarray(words), jnp.asarray(kc), unpack_backend=resolved
                )
            )
            out[
                start * BLOCK_VALUES : start * BLOCK_VALUES + rows * BLOCK_VALUES
            ] = dec[:rows].reshape(-1)
    return out[:count]
