"""Plain reference decoder for WZRC v2 containers of 2-D 5/3 pyramids.

Written from the container layout and ITU-T T.800 alone, in numpy, and
imports nothing of the system under test: it parses the header, checks
every CRC, Rice-decodes each band and runs the reversible 5/3 inverse
(Annex F, whole-point symmetric extension) level by level.  The
benchmark holds the system's coded bytes to it: a lossless codec's
bytes must come back as the very samples that went in.

Container layout (little-endian)::

    "WZRC" u8 version=2, kind=2 (2-D), flags, mode (1 = jpeg2000),
    dtype, levels, ndim=2, nlead, u16 block=256, u8 qmax=8, u8 kmax=24,
    u8 len + scheme name, nlead x u32 lead dims, 2 x u32 (H, W),
    nbands x u32 blob lengths, nbands x u32 blob CRCs,
    u32 parity length, u32 parity CRC, u32 CRC of all the above,
    then the band blobs (LL, then (LH, HL, HH) coarsest level first).

A band blob is ``nblocks`` Rice parameters (u8), ``nblocks`` byte
lengths (u16) and the byte-aligned block bitstreams.  Each of a block's
256 values is a zigzag-mapped ``u`` coded MSB first as ``q = u >> k``
ones, a zero and ``k`` remainder bits, or, where ``q >= 8``, eight ones
and the 32 raw bits of ``u``.
"""
from __future__ import annotations

import struct
import zlib
from typing import List, Optional, Tuple

import numpy as np

MAGIC = b"WZRC"
BLOCK = 256
QMAX = 8
KMAX = 24
ESCAPE_BITS = QMAX + 32
_HEAD = struct.Struct("<4sBBBBBBBBHBB")
_MODES = {0: "paper", 1: "jpeg2000"}

# leading ones of a byte, 0..8: the unary quotient, capped where it escapes
_LEADING_ONES = np.array(
    [8 - len(bin(b ^ 0xFF)[2:]) if b != 0xFF else 8 for b in range(256)],
    dtype=np.uint64,
)


class ContainerError(ValueError):
    """A container the reference cannot read as a valid 2-D WZRC v2."""


class Header(dict):
    """Parsed header fields (a dict with attribute access)."""

    __getattr__ = dict.__getitem__


def parse_header(data: bytes) -> Header:
    """Header fields, with every field and the header CRC checked."""
    if len(data) < _HEAD.size or data[:4] != MAGIC:
        raise ContainerError("not a WZRC container")
    (_, version, kind, _flags, mode, _dtype, levels, nd, nlead, block, qmax,
     kmax) = _HEAD.unpack_from(data, 0)
    if (version, kind, nd) != (2, 2, 2):
        raise ContainerError(f"version/kind/ndim {version}/{kind}/{nd}, want 2/2/2")
    if (block, qmax, kmax) != (BLOCK, QMAX, KMAX):
        raise ContainerError(f"Rice geometry {(block, qmax, kmax)}")
    if mode not in _MODES:
        raise ContainerError(f"mode code {mode}")
    off = _HEAD.size
    slen = data[off]
    scheme = data[off + 1: off + 1 + slen].decode("ascii", "replace")
    off += 1 + slen
    lead = struct.unpack_from(f"<{nlead}I", data, off)
    off += 4 * nlead
    shape = struct.unpack_from("<2I", data, off)
    off += 8
    nbands = 1 + 3 * levels
    blob_lens = struct.unpack_from(f"<{nbands}I", data, off)
    off += 4 * nbands
    crcs = struct.unpack_from(f"<{nbands}I", data, off)
    off += 4 * nbands
    parity_len, _parity_crc, header_crc = struct.unpack_from("<3I", data, off)
    if zlib.crc32(data[: off + 8]) & 0xFFFFFFFF != header_crc:
        raise ContainerError("header CRC mismatch")
    off += 12
    if off + sum(blob_lens) + parity_len != len(data):
        raise ContainerError("body length disagrees with the band table")
    return Header(
        mode=_MODES[mode], scheme=scheme, levels=levels, lead=tuple(lead),
        shape=tuple(shape), blob_lens=blob_lens, crcs=crcs, body=off,
    )


def band_shapes(h: int, w: int, levels: int) -> List[Tuple[int, int]]:
    """Band shapes in pack order: LL, then (LH, HL, HH) coarsest first."""
    per_level = []
    for _ in range(levels):
        per_level.append([(h // 2, -(-w // 2)), (-(-h // 2), w // 2), (h // 2, w // 2)])
        h, w = -(-h // 2), -(-w // 2)
    out = [(h, w)]
    for triple in reversed(per_level):
        out.extend(triple)
    return out


def rice_decode(blob: bytes, count: int) -> np.ndarray:
    """Decode one band blob to ``count`` int64 samples.

    Every block is decoded in lockstep, one value of each per step, from
    a 64-bit big-endian window at the block's current bit offset.
    """
    nb = -(-count // BLOCK)
    if len(blob) < 3 * nb:
        raise ContainerError("band blob shorter than its tables")
    ks = np.frombuffer(blob, np.uint8, nb).astype(np.uint64)
    lens = np.frombuffer(blob, "<u2", nb, offset=nb).astype(np.int64)
    payload = np.frombuffer(blob, np.uint8, offset=3 * nb)
    if int(lens.sum()) != payload.size:
        raise ContainerError("block lengths disagree with the payload")
    padded = np.concatenate([payload, np.zeros(8, np.uint8)])
    windows = np.lib.stride_tricks.sliding_window_view(padded, 8)
    start = np.concatenate([[0], np.cumsum(lens)[:-1]]) * 8  # bit offsets
    off = np.zeros(nb, np.int64)
    out = np.empty((nb, BLOCK), np.uint64)
    has_k = ks > 0
    k_shift = np.where(has_k, np.uint64(64) - ks, np.uint64(0))
    for j in range(BLOCK):
        bit = start + off
        w = np.ascontiguousarray(windows[bit >> 3]).view(">u8")[:, 0].astype(np.uint64)
        w = w << (bit & 7).astype(np.uint64)
        q = _LEADING_ONES[(w >> np.uint64(56)).astype(np.intp)]
        esc = q >= QMAX
        rem = np.where(has_k, (w << (q + np.uint64(1))) >> k_shift, np.uint64(0))
        u = np.where(esc, (w << np.uint64(QMAX)) >> np.uint64(32), (q << ks) | rem)
        out[:, j] = u
        off += np.where(esc, ESCAPE_BITS, (q + np.uint64(1) + ks).astype(np.int64))
    if np.any(off > lens * 8):
        raise ContainerError("a block's codes run past its byte length")
    u = out.reshape(-1)[:count].astype(np.int64)
    return (u >> 1) ^ -(u & 1)  # zigzag back to signed


def _inverse_axis(s: np.ndarray, d: np.ndarray, axis: int) -> np.ndarray:
    """Reversible 5/3 synthesis along ``axis`` (T.800 F.3.8, jpeg2000)."""
    s = np.moveaxis(s, axis, -1)
    d = np.moveaxis(d, axis, -1)
    ns, nd = s.shape[-1], d.shape[-1]
    n = ns + nd
    if nd == 0:
        return np.moveaxis(s, -1, axis)
    # even[k] = s[k] - floor((d[k-1] + d[k] + 2) / 4); d[-1] mirrors to d[0]
    d_left = np.concatenate([d[..., :1], d], axis=-1)[..., :ns]
    d_right = d if nd == ns else np.concatenate([d, d[..., -1:]], axis=-1)
    even = s - ((d_left + d_right + 2) >> 2)
    # odd[k] = d[k] + floor((even[k] + even[k+1]) / 2); even[ns] mirrors
    e_next = even[..., 1:] if ns > nd else np.concatenate(
        [even[..., 1:], even[..., -1:]], axis=-1
    )
    odd = d + ((even[..., :nd] + e_next[..., :nd]) >> 1)
    x = np.empty(s.shape[:-1] + (n,), np.int64)
    x[..., 0::2] = even
    x[..., 1::2] = odd
    return np.moveaxis(x, -1, axis)


def inverse_2d(bands: List[np.ndarray], levels: int) -> np.ndarray:
    """Multi-level inverse: columns then rows at each level."""
    ll = bands[0]
    for i in range(levels):
        lh, hl, hh = bands[1 + 3 * i: 4 + 3 * i]
        low = _inverse_axis(ll, lh, axis=-2)
        high = _inverse_axis(hl, hh, axis=-2)
        ll = _inverse_axis(low, high, axis=-1)
    return ll


def decode(data: bytes, *, levels: int, mode: str = "jpeg2000",
           scheme: str = "cdf53") -> np.ndarray:
    """Samples of a batch container, shape ``lead + (H, W)``, int64.

    Raises :class:`ContainerError` where the container is malformed, a
    CRC fails, or it states another scheme, mode or depth than the
    deployment's.
    """
    h = parse_header(data)
    if (h.scheme, h.mode, h.levels) != (scheme, mode, levels):
        raise ContainerError(
            f"container states {h.scheme}/{h.mode}/{h.levels} levels, "
            f"deployment is {scheme}/{mode}/{levels}"
        )
    lead_n = int(np.prod(h.lead)) if h.lead else 1
    bands = []
    off = h.body
    for blen, crc, shp in zip(h.blob_lens, h.crcs, band_shapes(*h.shape, h.levels)):
        blob = data[off: off + blen]
        off += blen
        if zlib.crc32(blob) & 0xFFFFFFFF != crc:
            raise ContainerError("band CRC mismatch")
        flat = rice_decode(blob, lead_n * shp[0] * shp[1])
        bands.append(flat.reshape(h.lead + shp))
    return inverse_2d(bands, h.levels)


def mismatches(decoded: Optional[np.ndarray], index: Optional[int],
               image: np.ndarray) -> int:
    """Samples of ``image`` that row ``index`` of a decoded container
    gets wrong; a missing (``None``) or misshapen answer gets every
    sample wrong."""
    arr = decoded
    if arr is not None and index is not None:
        arr = arr[index] if arr.ndim == 3 and 0 <= index < arr.shape[0] else None
    if arr is None or arr.ndim != 2 or any(a < b for a, b in zip(arr.shape, image.shape)):
        return int(image.size)
    crop = arr[: image.shape[0], : image.shape[1]]
    return int(np.count_nonzero(crop != image))


def decode_or_none(data: Optional[bytes], **kw) -> Optional[np.ndarray]:
    """:func:`decode`, with ``None`` for no bytes or an unreadable container
    (a header cut short, or tables that do not fit, included)."""
    if data is None:
        return None
    try:
        return decode(data, **kw)
    except (ValueError, IndexError, struct.error):  # ContainerError is a ValueError
        return None
