"""Plain reference decoder for WZRC v2 containers of 2-D and 3-D 5/3 pyramids.

Written from the container layout and ITU-T T.800 alone, in numpy, and
imports nothing of the system under test: it parses the header, checks
every CRC, Rice-decodes each band and runs the reversible 5/3 inverse
(Annex F, whole-point symmetric extension) level by level.  The
benchmark holds the system's coded bytes to it: a lossless codec's
bytes must come back as the very samples that went in.

Container layout (little-endian)::

    "WZRC" u8 version=2, kind, flags, mode (1 = jpeg2000), dtype,
    levels, ndim, nlead (0 or 1), u16 block=256, u8 qmax=8, u8 kmax=24,
    u8 len + scheme name, nlead x u32 lead dims, ndim x u32 dims,
    nbands x u32 blob lengths, nbands x u32 blob CRCs,
    u32 parity length, u32 parity CRC, u32 CRC of all the above,
    then the band blobs in pack order.

Two kinds are read: kind 2 with ndim 2 (an image, dims (H, W)) and
kind 3 with ndim 3 (a volume, dims (D, H, W)).  A band is named by its
code: bit ``j`` set means highpass along axis ``-(j + 1)``, so code 0 is
the approximation.  Along each axis a level splits ``n`` samples into
``ceil(n / 2)`` lowpass and ``floor(n / 2)`` highpass ones.  Pack order
is the approximation, then each level from the coarsest to the finest
with its ``2**ndim - 1`` detail bands:

- kind 2 in the order LH, HL, HH, codes 2, 1, 3;
- kind 3 in code order 1, 2, ..., 7.

So ``nbands = 1 + (2**ndim - 1) * levels``.  The band arrays hold the
lead dim before their own dims, and each is coded as one flat stream.

A band blob is ``nblocks`` Rice parameters (u8), ``nblocks`` byte
lengths (u16) and the byte-aligned block bitstreams.  Each of a block's
256 values is a zigzag-mapped ``u`` coded MSB first as ``q = u >> k``
ones, a zero and ``k`` remainder bits, or, where ``q >= 8``, eight ones
and the 32 raw bits of ``u``.

The inverse undoes a level axis by axis, the last-transformed axis
first: the forward transform runs axis -1, then -2, then -3, so the
inverse runs axis -3 (pairing codes ``c`` and ``c | 4``), then axis -2
(``c`` and ``c | 2``), then axis -1 (``0`` and ``1``).  Every sample is
int64 from the Rice decode on, which no 5/3 coefficient of 16-bit
samples can leave.
"""
from __future__ import annotations

import math
import struct
import zlib
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

MAGIC = b"WZRC"
BLOCK = 256
QMAX = 8
KMAX = 24
ESCAPE_BITS = QMAX + 32
_HEAD = struct.Struct("<4sBBBBBBBBHBB")
_MODES = {0: "paper", 1: "jpeg2000"}
# the ndim each kind read here states, and its detail bands' codes in pack order
_KIND_NDIM = {2: 2, 3: 3}
PACK_CODES = {2: (2, 1, 3), 3: tuple(range(1, 8))}
# rows of a batch container decoded at once, as many as hold this many
# samples (one at the least): a 512x512x256 volume decodes alone, the
# 2-D batches whole
ROW_GROUP_SAMPLES = 2**24

# leading ones of a byte, 0..8: the unary quotient, capped where it escapes
_LEADING_ONES = np.array(
    [8 - len(bin(b ^ 0xFF)[2:]) if b != 0xFF else 8 for b in range(256)],
    dtype=np.uint64,
)


class ContainerError(ValueError):
    """A container the reference cannot read as a valid 2-D or 3-D WZRC v2."""


class Header(dict):
    """Parsed header fields (a dict with attribute access)."""

    __getattr__ = dict.__getitem__


def parse_header(data: bytes) -> Header:
    """Header fields, with every field and the header CRC checked."""
    if len(data) < _HEAD.size or data[:4] != MAGIC:
        raise ContainerError("not a WZRC container")
    (_, version, kind, _flags, mode, _dtype, levels, nd, nlead, block, qmax,
     kmax) = _HEAD.unpack_from(data, 0)
    if version != 2 or _KIND_NDIM.get(kind) != nd:
        raise ContainerError(
            f"version/kind/ndim {version}/{kind}/{nd}, want 2/2/2 or 2/3/3")
    if (block, qmax, kmax) != (BLOCK, QMAX, KMAX):
        raise ContainerError(f"Rice geometry {(block, qmax, kmax)}")
    if mode not in _MODES:
        raise ContainerError(f"mode code {mode}")
    if nlead > 1:
        raise ContainerError(f"{nlead} lead dims, a batch container has one")
    off = _HEAD.size
    slen = data[off]
    scheme = data[off + 1: off + 1 + slen].decode("ascii", "replace")
    off += 1 + slen
    lead = struct.unpack_from(f"<{nlead}I", data, off)
    off += 4 * nlead
    shape = struct.unpack_from(f"<{nd}I", data, off)
    off += 4 * nd
    nbands = 1 + len(PACK_CODES[nd]) * levels
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
        rows=lead[0] if lead else 1, shape=tuple(shape), blob_lens=blob_lens,
        crcs=crcs, body=off,
    )


def band_shapes(shape: Sequence[int], levels: int) -> List[Tuple[int, ...]]:
    """Band shapes in pack order, for the dims of a 2-D image or 3-D volume."""
    dims = list(shape)
    nd = len(dims)
    per_level = []
    for _ in range(levels):
        low = [-(-n // 2) for n in dims]
        high = [n // 2 for n in dims]
        # axis i (from the left) is axis -(nd - i): code bit nd - 1 - i
        per_level.append([
            tuple(high[i] if code >> (nd - 1 - i) & 1 else low[i] for i in range(nd))
            for code in PACK_CODES[nd]
        ])
        dims = low
    out = [tuple(dims)]
    for lvl in reversed(per_level):
        out.extend(lvl)
    return out


def rice_decode(blob: bytes, count: int, start: int = 0,
                stop: Optional[int] = None) -> np.ndarray:
    """Samples ``start:stop`` of a band blob of ``count``, as int64.

    Only the blocks that hold them are decoded, in lockstep, one value of
    each per step, from a 64-bit big-endian window at the block's
    current bit offset; the blob's tables are checked whole.
    """
    stop = count if stop is None else stop
    nb = -(-count // BLOCK)
    if len(blob) < 3 * nb:
        raise ContainerError("band blob shorter than its tables")
    lens = np.frombuffer(blob, "<u2", nb, offset=nb).astype(np.int64)
    payload = np.frombuffer(blob, np.uint8, offset=3 * nb)
    if int(lens.sum()) != payload.size:
        raise ContainerError("block lengths disagree with the payload")
    b0, b1 = start // BLOCK, -(-stop // BLOCK)
    ks = np.frombuffer(blob, np.uint8, b1 - b0, offset=b0).astype(np.uint64)
    begin = np.concatenate([[0], np.cumsum(lens)])[b0:b1]
    lens = lens[b0:b1]
    payload = payload[begin[0]: begin[-1] + lens[-1]] if b1 > b0 else payload[:0]
    padded = np.concatenate([payload, np.zeros(8, np.uint8)])
    windows = np.lib.stride_tricks.sliding_window_view(padded, 8)
    start_bit = (begin - begin[:1]) * 8  # bit offsets in the blocks' payload
    off = np.zeros(b1 - b0, np.int64)
    out = np.empty((b1 - b0, BLOCK), np.uint64)
    has_k = ks > 0
    k_shift = np.where(has_k, np.uint64(64) - ks, np.uint64(0))
    for j in range(BLOCK):
        bit = start_bit + off
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
    u = out.reshape(-1)[start - b0 * BLOCK: stop - b0 * BLOCK].astype(np.int64)
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


def inverse(bands: List[np.ndarray], levels: int, ndim: int) -> np.ndarray:
    """Multi-level inverse of bands in pack order over the last ``ndim``
    axes: at each level, axis -ndim first, axis -1 last.  Consumes
    ``bands``, so each band is freed once it is merged."""
    cur = bands[0]
    nper = len(PACK_CODES[ndim])
    for i in range(levels):
        level = [cur] + [None] * nper
        for code, b in zip(PACK_CODES[ndim], bands[1 + nper * i: 1 + nper * (i + 1)]):
            level[code] = b
        bands[1 + nper * i: 1 + nper * (i + 1)] = [None] * nper
        for j in reversed(range(ndim)):
            half = 1 << j
            for c in range(half):
                level[c] = _inverse_axis(level[c], level[c | half], axis=-(j + 1))
                level[c | half] = None
            del level[half:]
        cur = level[0]
    return cur


def _open(data: bytes, levels: int, mode: str, scheme: str):
    """The header and the CRC-checked band blobs, where the container
    states the deployment's scheme, mode and depth."""
    h = parse_header(data)
    if (h.scheme, h.mode, h.levels) != (scheme, mode, levels):
        raise ContainerError(
            f"container states {h.scheme}/{h.mode}/{h.levels} levels, "
            f"deployment is {scheme}/{mode}/{levels}"
        )
    view = memoryview(data)
    blobs, off = [], h.body
    for blen, crc in zip(h.blob_lens, h.crcs):
        blob = view[off: off + blen]
        off += blen
        if zlib.crc32(blob) & 0xFFFFFFFF != crc:
            raise ContainerError("band CRC mismatch")
        blobs.append(blob)
    return h, blobs


def _decode_rows(h: Header, blobs, r0: int, r1: int) -> np.ndarray:
    """Rows ``r0:r1`` of the batch, shape ``(r1 - r0,) + dims``."""
    bands = []
    for blob, shp in zip(blobs, band_shapes(h.shape, h.levels)):
        n = math.prod(shp)
        bands.append(rice_decode(blob, h.rows * n, r0 * n, r1 * n).reshape((r1 - r0,) + shp))
    return inverse(bands, h.levels, len(h.shape))


def decode(data: bytes, *, levels: int, mode: str = "jpeg2000",
           scheme: str = "cdf53") -> np.ndarray:
    """Samples of a container, shape ``lead + dims``, int64.

    Raises :class:`ContainerError` where the container is malformed, a
    CRC fails, or it states another scheme, mode or depth than the
    deployment's.
    """
    h, blobs = _open(data, levels, mode, scheme)
    return _decode_rows(h, blobs, 0, h.rows).reshape(h.lead + h.shape)


def decode_rows(data: bytes, *, levels: int, mode: str = "jpeg2000",
                scheme: str = "cdf53") -> Iterator[Tuple[Optional[int], np.ndarray]]:
    """``(row, samples)`` for each row of a container, ``row`` ``None``
    where it has no lead dim; the rows are decoded a group of
    ``ROW_GROUP_SAMPLES`` at a time, so a batch of volumes is never
    held whole.  Raises as :func:`decode` does."""
    h, blobs = _open(data, levels, mode, scheme)
    step = max(1, ROW_GROUP_SAMPLES // max(math.prod(h.shape), 1))
    for r0 in range(0, h.rows, step):
        r1 = min(r0 + step, h.rows)
        group = _decode_rows(h, blobs, r0, r1)
        for i in range(r1 - r0):
            yield (r0 + i if h.lead else None), group[i]
        del group  # freed before the next group is decoded


def mismatches(decoded: Optional[np.ndarray], index: Optional[int],
               image: np.ndarray) -> int:
    """Samples of ``image`` that ``decoded`` gets wrong: its row ``index``
    where ``index`` is given, itself otherwise, cropped to the image.  A
    missing (``None``) or misshapen answer gets every sample wrong."""
    arr = decoded
    if arr is not None and index is not None:
        ok = arr.ndim == image.ndim + 1 and 0 <= index < arr.shape[0]
        arr = arr[index] if ok else None
    if arr is None or arr.ndim != image.ndim or any(
            a < b for a, b in zip(arr.shape, image.shape)):
        return int(image.size)
    crop = arr[tuple(slice(0, s) for s in image.shape)]
    return int(np.count_nonzero(crop != image))


def container_mismatches(data: bytes, rows: Sequence[Tuple[Optional[int], np.ndarray]],
                         **kw) -> int:
    """Samples that one container gets wrong of the images it answers:
    each ``(row, image)`` against the row it names (``None`` for a
    container with no lead dim).  An image whose row the container does
    not hold, or every image where the container does not decode, gets
    every sample wrong, a header cut short or tables that do not fit
    included.  The container is decoded a group of rows at a time
    (:func:`decode_rows`)."""
    wanted = {}
    for row, image in rows:
        wanted.setdefault(row, []).append(image)
    wrong = 0
    try:
        for row, samples in decode_rows(data, **kw):
            for image in wanted.pop(row, []):
                wrong += mismatches(samples, None, image)
            del samples  # so that one group is held at a time
    except (ValueError, IndexError, struct.error):  # ContainerError is a ValueError
        return sum(int(image.size) for _, image in rows)
    return wrong + sum(int(image.size) for images in wanted.values() for image in images)
