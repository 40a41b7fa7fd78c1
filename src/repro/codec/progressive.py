"""Progressive, byte-range decode of WZRC containers.

The container header (PR 5) records every band blob's byte length, so a
reader can seek straight to any band — but until PR 8 every decode path
read the WHOLE blob.  This module exploits the offset table: one stored
bitstream serves many fidelity tiers, each tier touching only the byte
ranges it needs.

    decode_lowband(src)             the approximation band alone — the
                                    thumbnail/preview tier; reads the
                                    header plus ONE band blob
    decode_band(src, index)         any single band in pack order
    decode_progressive(src, L)      approx + the coarsest L detail
                                    levels, assembled as a valid
                                    pyramid with ``levels == L`` —
                                    inverse-transform it and you hold
                                    the level-(levels-L) approximation
                                    (each increment of L doubles the
                                    reconstructed resolution per axis)

``src`` is either ``bytes`` or any object with ``pread(offset, size)``
(positional read) — a file, an object-store ranged GET, or the
:class:`CountingReader` the byte-accounting tests use.  Every tier
re-verifies the header CRC and the CRCs of exactly the bands it reads
(v2 containers); a band that fails its CRC heals from the XOR parity
group when present (``heal=True`` — this is the one path that reads the
full body, correctness over bandwidth), quarantines zero-filled under
``partial=True``, and raises :class:`~repro.codec.errors.CorruptBandError`
otherwise.  A corrupt refinement band therefore never poisons the
thumbnail tier: coarser tiers decode from their own (intact) ranges.

Batch containers (``codec.encode_batch``; lead dim = micro-batch) work
unchanged — every band decodes to ``(B, ...)``, so one stored serve
response yields B thumbnails from one ranged read.
"""
from __future__ import annotations

import zlib
from typing import Any, List, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.codec import container as C
from repro.codec.errors import (
    CodecError,
    CorruptBandError,
    CorruptHeaderError,
)

__all__ = [
    "BandDecode",
    "CountingReader",
    "band_byte_ranges",
    "decode_band",
    "decode_lowband",
    "decode_progressive",
    "read_header",
    "reconstruct",
]


# ---------------------------------------------------------------------------
# Byte-range sources.
# ---------------------------------------------------------------------------


class _BytesReader:
    """``pread`` view over an in-memory blob."""

    def __init__(self, data: bytes):
        self._data = bytes(data)

    def pread(self, offset: int, size: int) -> bytes:
        return self._data[offset : offset + size]


class CountingReader:
    """A ``pread`` source that accounts every byte it hands out.

    The progressive-decode tests wrap the container in one of these and
    assert that the thumbnail tier reads strictly fewer bytes than the
    blob holds — i.e. that partial decode is *measurably* partial, not
    a full read with a partial return value.
    """

    def __init__(self, data: bytes):
        self._data = bytes(data)
        self.bytes_read = 0
        self.reads = 0

    def __len__(self) -> int:
        return len(self._data)

    def pread(self, offset: int, size: int) -> bytes:
        chunk = self._data[offset : offset + size]
        self.reads += 1
        self.bytes_read += len(chunk)
        return chunk


def _reader(src: Any):
    if hasattr(src, "pread"):
        return src
    if isinstance(src, (bytes, bytearray, memoryview)):
        return _BytesReader(bytes(src))
    raise TypeError(
        f"need bytes or an object with pread(offset, size), got {type(src)!r}"
    )


# ---------------------------------------------------------------------------
# Header: staged ranged reads, then the container module's own parser.
# ---------------------------------------------------------------------------


def read_header(src: Any) -> C._Header:
    """Parse a container header from ranged reads alone.

    Reads the fixed head (+ the scheme-length byte), derives the exact
    variable-tail size from it, reads that tail, and hands the prefix to
    ``container._parse_header`` — one parser, two access patterns.  The
    v2 header CRC is verified exactly as in a full decode.
    """
    r = _reader(src)
    fixed = r.pread(0, C._HEAD.size + 1)
    if len(fixed) < C._HEAD.size + 1 or fixed[:4] != C.MAGIC:
        raise CorruptHeaderError("not a WZRC container (bad magic)")
    (_, version, kind, _flags, _mode, _dt, levels, nd, nlead, _b, _q, _k) = (
        C._HEAD.unpack_from(fixed, 0)
    )
    slen = fixed[C._HEAD.size]
    if kind == C.KIND_1D:
        nbands = 1 + levels
    elif kind == C.KIND_2D:
        nbands = 1 + 3 * levels
    else:
        nbands = 1 + ((1 << nd) - 1) * levels
    tail = slen + 4 * nlead + 4 * nd + 4 * nbands
    if version >= 2:
        tail += 4 * nbands + 8 + 4  # band CRCs, parity (len, crc), header CRC
    prefix = fixed + r.pread(len(fixed), tail)
    return C._parse_header(prefix)


def band_byte_ranges(h: C._Header) -> List[Tuple[int, int]]:
    """Per-band ``(offset, length)`` into the container, pack order."""
    out = []
    off = h.body_off
    for blen in h.blob_lens:
        out.append((off, blen))
        off += blen
    return out


def _band_count(h: C._Header, up_to_level: int) -> int:
    per = {C.KIND_1D: 1, C.KIND_2D: 3}.get(h.kind, (1 << h.ndim) - 1)
    return 1 + per * up_to_level


# ---------------------------------------------------------------------------
# Band reads: CRC per band, parity healing, quarantine.
# ---------------------------------------------------------------------------


def _heal_from_parity(r, h: C._Header, index: int) -> Optional[bytes]:
    """Reconstruct band ``index`` from the XOR parity group.

    Reads the full body (every intact band + the parity blob) — the one
    progressive path that is not partial, because healing is defined
    over the whole group.  Returns ``None`` when parity is absent,
    damaged, or more than this band is broken.
    """
    if not h.parity_len:
        return None
    ranges = band_byte_ranges(h)
    parity_off = h.body_off + sum(h.blob_lens)
    parity = r.pread(parity_off, h.parity_len)
    if zlib.crc32(parity) & 0xFFFFFFFF != h.parity_crc:
        return None
    acc = np.frombuffer(parity, np.uint8).copy()
    for i, (off, blen) in enumerate(ranges):
        if i == index:
            continue
        blob = r.pread(off, blen)
        if zlib.crc32(blob) & 0xFFFFFFFF != h.band_crcs[i]:
            return None  # two damaged bands: XOR cannot isolate either
        arr = np.frombuffer(blob, np.uint8)
        acc[: len(arr)] ^= arr
    rec = acc.tobytes()[: h.blob_lens[index]]
    if zlib.crc32(rec) & 0xFFFFFFFF != h.band_crcs[index]:
        return None
    return rec


def _read_band_blob(
    r, h: C._Header, index: int, heal: bool
) -> Tuple[Optional[bytes], str]:
    """One band's verified bytes -> (blob | None, band status)."""
    off, blen = band_byte_ranges(h)[index]
    blob = r.pread(off, blen)
    if len(blob) != blen:
        blob = None  # truncated source
    if h.version >= 2 and blob is not None:
        if zlib.crc32(blob) & 0xFFFFFFFF != h.band_crcs[index]:
            blob = None
    if blob is not None:
        return blob, C.BAND_OK
    if heal and h.version >= 2:
        rec = _heal_from_parity(r, h, index)
        if rec is not None:
            return rec, C.BAND_RECONSTRUCTED
    return None, C.BAND_CORRUPT


def _decode_one(
    r, h: C._Header, index: int, heal: bool, partial: bool
) -> Tuple[jnp.ndarray, str]:
    shapes = C._expected_band_shapes(h.kind, h.shape, h.levels)
    lead_n = 1
    for s in h.lead:
        lead_n *= s
    count = lead_n
    for s in shapes[index]:
        count *= s
    blob, status = _read_band_blob(r, h, index, heal)
    if blob is not None:
        try:
            flat = C._decode_band_blob(blob, count)
        except (CodecError, ValueError):
            blob, status = None, C.BAND_CORRUPT
    if blob is None:
        if not partial:
            raise CorruptBandError(
                f"WZRC band {index} corrupt and unrecoverable "
                f"({'parity absent' if not h.parity_len else 'parity could not heal'})",
                band_status=(status,),
            )
        flat = np.zeros(count, np.int32)
    band = jnp.asarray(flat.astype(h.dtype).reshape(h.lead + shapes[index]))
    return band, status


class BandDecode(NamedTuple):
    """One band plus the container self-description it decoded under."""

    band: Any  # (lead..., band shape) array
    index: int  # pack-order band index
    status: str  # "ok" | "reconstructed"
    kind: int
    scheme: str
    mode: str
    levels: int  # the CONTAINER's level count, not a tier
    lead: Tuple[int, ...]
    shape: Tuple[int, ...]
    dtype: np.dtype


def decode_band(src: Any, index: int, *, heal: bool = True) -> BandDecode:
    """Decode a single band (pack order) from its byte range alone.

    Pack order is approx first, then per-level detail bands coarsest to
    finest — index 0 is always the approximation band.  CRC-verified
    (v2); a damaged band heals from parity when ``heal`` (reading the
    full body) and raises :class:`CorruptBandError` otherwise.
    """
    r = _reader(src)
    h = read_header(r)
    if not 0 <= index < len(h.blob_lens):
        raise ValueError(
            f"band index {index} out of range ({len(h.blob_lens)} bands)"
        )
    band, status = _decode_one(r, h, index, heal, partial=False)
    return BandDecode(
        band=band, index=index, status=status, kind=h.kind, scheme=h.scheme,
        mode=h.mode, levels=h.levels, lead=h.lead, shape=h.shape,
        dtype=h.dtype,
    )


def decode_lowband(src: Any, *, heal: bool = True) -> BandDecode:
    """The approximation band alone — the thumbnail tier.

    Reads the header plus one band blob; for an L-level 2D container
    that is roughly a ``4^-L`` fraction of the samples and whatever the
    coder spent on them.  The returned band IS the low-resolution
    image (the integer DWT's approx channel), no inverse needed.
    """
    return decode_band(src, 0, heal=heal)


def decode_progressive(
    src: Any,
    up_to_level: int,
    *,
    heal: bool = True,
    partial: bool = False,
) -> C.DecodedPyramid:
    """Decode the coarsest ``up_to_level`` detail levels (plus approx).

    Returns a valid pyramid with ``levels == up_to_level`` — exactly the
    full decode's pyramid truncated to its coarsest levels, bit for bit
    — reading only the byte ranges of the bands it returns.
    ``up_to_level=0`` is the thumbnail tier as a (levels-0) pyramid;
    ``up_to_level == container levels`` reads everything and equals the
    full decode.  ``partial=True`` quarantines damaged in-range bands
    zero-filled (status ``"corrupt"``) instead of raising, so a clean
    coarse tier survives a damaged refinement range.

    Inverse-transform the result (``container.inverse_transform`` /
    ``progressive.reconstruct``) to hold the level-``(levels - L)``
    approximation of the original samples.
    """
    r = _reader(src)
    h = read_header(r)
    if not 0 <= up_to_level <= h.levels:
        raise ValueError(
            f"up_to_level must be in [0, {h.levels}], got {up_to_level}"
        )
    n = _band_count(h, up_to_level)
    bands = []
    status: List[str] = []
    for i in range(n):
        band, st = _decode_one(r, h, i, heal, partial)
        bands.append(band)
        status.append(st)
    trunc = h._replace(levels=up_to_level)
    return C.DecodedPyramid(
        pyramid=C._assemble(trunc, bands),
        kind=h.kind,
        scheme=h.scheme,
        mode=h.mode,
        levels=up_to_level,
        lead=h.lead,
        shape=h.shape,
        dtype=h.dtype,
        band_status=tuple(status),
    )


def reconstruct(dec: C.DecodedPyramid, backend: Optional[str] = None):
    """Inverse-transform a (possibly truncated) decode to samples.

    For a :func:`decode_progressive` tier this yields the approximation
    at the tier's resolution; for a full decode, the original samples
    bit-exactly.  Levels-0 decodes (the thumbnail tier) return the
    approx band unchanged.
    """
    if dec.levels == 0:
        return dec.pyramid.approx if hasattr(dec.pyramid, "approx") else dec.pyramid.ll
    with obs.span("codec.inverse", subsystem="codec"):  # dispatch, no sync
        return C.inverse_transform(dec, backend=backend)
