"""Self-describing bitstream container for integer wavelet pyramids.

One blob = one pyramid.  The header carries everything needed to decode
from bytes alone — magic/version, pyramid kind (1D ``WaveletPyramid``,
2D ``Pyramid2D``, N-D ``PyramidND``), lifting scheme and rounding mode,
levels, band dtype, leading (batch) dims and the original trailing
shape — followed by one Rice blob per band in pack order (approx first,
then per-level detail bands coarsest->finest).  Band geometry is a pure
function of (shape, levels), so band sizes are never serialized; per-band
blob byte lengths ARE, so a reader can seek straight to any band.

Version 1 layout (little-endian; still decoded, still writable via
``encode_pyramid(version=1)`` for v1 readers)::

    magic   4s   b"WZRC"
    version u8   1
    kind    u8   1 = WaveletPyramid, 2 = Pyramid2D, 3 = PyramidND
    flags   u8   bit0: crc32 trailer present
    mode    u8   0 = paper, 1 = jpeg2000
    dtype   u8   1 = int8, 2 = int16, 3 = int32
    levels  u8
    ndim    u8   trailing transform axes (1 for kind 1, 2 for kind 2)
    nlead   u8
    block   u16  rice.BLOCK_VALUES  } coder geometry, so a future build
    qmax    u8   rice.Q_MAX         } with different constants rejects
    kmax    u8   rice.K_MAX         } cleanly instead of mis-decoding
    lead    nlead x u32
    shape   ndim x u32
    blob_len  nbands x u32
    blobs   concatenated band blobs: [k u8 x nblocks][len u16 x nblocks]
            [byte-aligned Rice bitstream]
    crc32   u32  zlib.crc32 of everything above (when flags bit0)

Version 2 (the default) replaces the single whole-blob CRC — under
which one flipped bit anywhere loses every band — with *localized*
integrity plus optional self-healing::

    ... same fixed fields (version=2, flags reserved 0) ...
    lead / shape / blob_len      as v1
    band_crc    nbands x u32     crc32 of each band blob
    parity_len  u32              0 = no parity group
    parity_crc  u32              crc32 of the parity blob (0 when none)
    header_crc  u32              crc32 of every byte above
    blobs                        concatenated band blobs (as v1)
    parity blob                  XOR of all band blobs zero-padded to
                                 parity_len (= max band blob length)

Decode verifies the header CRC first (a damaged header is never
partial: geometry lives there), then each band against its own CRC.  A
band that fails quarantines alone; with the parity group present, any
SINGLE damaged band reconstructs bit-exactly (XOR of the parity blob
with every intact band, truncated to the recorded length, re-verified
against the band's CRC).  ``decode_pyramid`` heals transparently and
records per-band status; ``decode_pyramid_partial`` additionally
returns the survivors (damaged bands zero-filled, status ``"corrupt"``)
instead of raising.  Every decode-side failure is a typed
:class:`~repro.codec.errors.CodecError` subclass — never a bare
``struct.error`` or ``IndexError``, and never a silently wrong band.

Every band blob is independently decodable (per-block k and byte
lengths travel with it), which is what the streaming layer, the serve
path and the parity reconstruction all lean on.
"""
from __future__ import annotations

import struct
import zlib
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.codec import rice
from repro.codec.errors import (
    CodecError,
    CorruptBandError,
    CorruptHeaderError,
    TruncatedStreamError,
    UnsupportedVersionError,
)
from repro.core import lifting, ranges
from repro.core.schemes import get_scheme

MAGIC = b"WZRC"
FORMAT_VERSION = 2
SUPPORTED_VERSIONS = (1, 2)

KIND_1D = 1
KIND_2D = 2
KIND_ND = 3

# per-band decode status values (DecodedPyramid.band_status)
BAND_OK = "ok"
BAND_RECONSTRUCTED = "reconstructed"
BAND_CORRUPT = "corrupt"

_MODES = {"paper": 0, "jpeg2000": 1}
_MODE_NAMES = {v: k for k, v in _MODES.items()}
_DTYPES = {np.dtype(np.int8): 1, np.dtype(np.int16): 2, np.dtype(np.int32): 3}
_DTYPE_NAMES = {v: k for k, v in _DTYPES.items()}

_HEAD = struct.Struct("<4sBBBBBBBBHBB")


class DecodedPyramid(NamedTuple):
    """A decoded container: the pyramid plus its self-description.

    ``band_status`` is one entry per band in pack order — ``"ok"`` or
    ``"reconstructed"`` (parity-healed, still bit-exact).  v1 blobs
    (whole-blob CRC only) report all-``"ok"``.
    """

    pyramid: Any  # WaveletPyramid | Pyramid2D | PyramidND
    kind: int
    scheme: str
    mode: str
    levels: int
    lead: Tuple[int, ...]
    shape: Tuple[int, ...]  # original trailing (pre-transform) shape
    dtype: np.dtype
    band_status: Tuple[str, ...] = ()


class PartialDecode(NamedTuple):
    """A quarantining decode: every recoverable band, plus per-band fate.

    ``band_status[i]`` is ``"ok"`` / ``"reconstructed"`` / ``"corrupt"``;
    corrupt bands are zero-filled in the pyramid (shape/dtype correct,
    content lost) so the structure stays a valid pyramid.
    """

    pyramid: Any
    kind: int
    scheme: str
    mode: str
    levels: int
    lead: Tuple[int, ...]
    shape: Tuple[int, ...]
    dtype: np.dtype
    band_status: Tuple[str, ...]

    @property
    def complete(self) -> bool:
        """True when every band decoded bit-exactly (incl. healed)."""
        return all(s != BAND_CORRUPT for s in self.band_status)


# ---------------------------------------------------------------------------
# Pyramid introspection: kind, band list in pack order, original shape.
# ---------------------------------------------------------------------------


def _pyramid_kind(pyr: Any) -> int:
    if isinstance(pyr, lifting.WaveletPyramid):
        return KIND_1D
    if isinstance(pyr, lifting.Pyramid2D):
        return KIND_2D
    if isinstance(pyr, lifting.PyramidND):
        return KIND_ND
    raise TypeError(
        f"expected WaveletPyramid / Pyramid2D / PyramidND, got {type(pyr)!r}"
    )


def _flatten_bands(pyr: Any, kind: int) -> List[np.ndarray]:
    """Bands in pack order (approx, then levels coarsest->finest)."""
    if kind == KIND_1D:
        return [np.asarray(pyr.approx)] + [np.asarray(d) for d in pyr.details]
    if kind == KIND_2D:
        out = [np.asarray(pyr.ll)]
        for lh, hl, hh in pyr.details:
            out.extend([np.asarray(lh), np.asarray(hl), np.asarray(hh)])
        return out
    out = [np.asarray(pyr.approx)]
    for lvl in pyr.details:
        out.extend(np.asarray(b) for b in lvl)
    return out


def _infer_geometry(
    pyr: Any, kind: int, ndim_hint: Optional[int]
) -> Tuple[int, Tuple[int, ...], Tuple[int, ...]]:
    """(ndim, lead_dims, original trailing shape) from the band shapes."""
    if kind == KIND_1D:
        n = pyr.approx.shape[-1] + sum(d.shape[-1] for d in pyr.details)
        return 1, tuple(pyr.approx.shape[:-1]), (n,)
    if kind == KIND_2D:
        h, w = pyr.ll.shape[-2], pyr.ll.shape[-1]
        for lh, hl, _hh in pyr.details:  # coarsest first
            h, w = h + lh.shape[-2], w + hl.shape[-1]
        return 2, tuple(pyr.ll.shape[:-2]), (h, w)
    if pyr.details:
        nd = pyr.ndim
        if ndim_hint is not None and ndim_hint != nd:
            raise ValueError(f"ndim={ndim_hint} but pyramid has ndim={nd}")
    elif ndim_hint is None:
        raise ValueError("levels=0 PyramidND: pass ndim explicitly")
    else:
        nd = ndim_hint
    dims = list(pyr.approx.shape[-nd:])
    for lvl in pyr.details:  # coarsest first; single-bit codes carry odds
        for j in range(nd):
            band = lvl[(1 << j) - 1]  # code (1 << j) at index code-1
            axis = nd - 1 - j
            dims[axis] += band.shape[-nd:][axis]
    return nd, tuple(pyr.approx.shape[:-nd]), tuple(dims)


def _expected_band_shapes(
    kind: int, shape: Tuple[int, ...], levels: int
) -> List[Tuple[int, ...]]:
    """Per-band trailing shapes in pack order — the decode geometry."""
    if kind == KIND_1D:
        a_len, d_lens = lifting.band_sizes(shape[0], levels)
        return [(a_len,)] + [(dl,) for dl in d_lens]
    if kind == KIND_2D:
        ll, det = lifting.band_shapes_2d(shape[0], shape[1], levels)
        out = [ll]
        for lvl in det:
            out.extend(lvl)
        return out
    approx, det = lifting.band_shapes_nd(tuple(shape), levels)
    out = [approx]
    for lvl in det:
        out.extend(lvl)
    return out


def _xor_parity(blobs: Sequence[bytes], plen: int) -> bytes:
    """XOR of the blobs zero-padded to ``plen`` — the parity group."""
    acc = np.zeros(plen, np.uint8)
    for b in blobs:
        arr = np.frombuffer(b, np.uint8)
        acc[: len(arr)] ^= arr
    return acc.tobytes()


# ---------------------------------------------------------------------------
# Encode.
# ---------------------------------------------------------------------------


def encode_pyramid(
    pyr: Any,
    scheme: str = "cdf53",
    mode: str = "paper",
    *,
    ndim: Optional[int] = None,
    backend: Optional[str] = None,
    checksum: bool = True,
    parity: bool = False,
    version: int = FORMAT_VERSION,
    checked: Optional[bool] = None,
) -> bytes:
    """Serialize an integer wavelet pyramid (see :func:`_encode_impl`).

    Instrumented entry point: the ``codec.encode_pyramid`` span, whose
    children are the per-band ``codec.encode_band`` spans, so its self
    time is the container assembly (band fetch, headers, CRCs); coded
    bytes count into ``codec.encode_bytes``.
    """
    with obs.span("codec.encode_pyramid", subsystem="codec"):
        out = _encode_impl(
            pyr, scheme, mode, ndim=ndim, backend=backend,
            checksum=checksum, parity=parity, version=version,
            checked=checked,
        )
    obs.counter("codec.encode_bytes").inc(len(out))
    return out


def _encode_impl(
    pyr: Any,
    scheme: str = "cdf53",
    mode: str = "paper",
    *,
    ndim: Optional[int] = None,
    backend: Optional[str] = None,
    checksum: bool = True,
    parity: bool = False,
    version: int = FORMAT_VERSION,
    checked: Optional[bool] = None,
) -> bytes:
    """Serialize an integer wavelet pyramid to a self-describing blob.

    Every band is Rice-coded independently (per-block adaptive ``k``);
    the result round-trips bit-exactly through :func:`decode_pyramid`
    from the bytes alone.  ``scheme``/``mode`` are recorded so a reader
    can run the inverse transform without out-of-band metadata; they do
    not affect the coded bytes of the bands themselves.

    ``version=2`` (default) writes per-band CRCs plus a header CRC so
    decode quarantines damage per band; ``parity=True`` additionally
    appends an XOR parity group sized to the largest band blob, letting
    any single damaged band reconstruct bit-exactly.  ``version=1``
    emits the legacy layout byte-for-byte (``checksum`` controls its
    whole-blob trailer) for v1 readers; v1 supports no parity.

    ``checked=True`` (or the ``REPRO_DWT_CHECKED`` env toggle) validates
    the bands against the scheme's derived int32 band-envelope
    certificate (``repro.core.ranges.assert_encodable``) before any byte
    is coded, so a bitstream this module emits is always one the
    recorded inverse transform can decode without integer wraparound —
    :class:`~repro.resilience.errors.IntegerOverflowError` instead of a
    container full of numbers only modulo arithmetic believes in.
    """
    kind = _pyramid_kind(pyr)
    if version not in SUPPORTED_VERSIONS:
        raise UnsupportedVersionError(
            f"cannot encode WZRC version {version} "
            f"(supports {SUPPORTED_VERSIONS})"
        )
    if parity and version < 2:
        raise ValueError("parity requires WZRC version 2")
    if mode not in _MODES:
        raise ValueError(f"mode must be one of {sorted(_MODES)}, got {mode!r}")
    nd, lead, shape = _infer_geometry(pyr, kind, ndim)
    levels = len(pyr.details)
    with obs.waiting():  # the transform that makes the bands may still run
        jax.block_until_ready(pyr)
    bands = _flatten_bands(pyr, kind)

    dt = np.dtype(bands[0].dtype)
    if dt not in _DTYPES:
        raise TypeError(
            f"band dtype must be one of {sorted(str(d) for d in _DTYPES)}, "
            f"got {dt}"
        )
    expected = _expected_band_shapes(kind, shape, levels)
    if len(bands) != len(expected):
        raise ValueError(
            f"malformed pyramid: {len(bands)} bands, geometry expects "
            f"{len(expected)}"
        )
    for band, want in zip(bands, expected):
        if np.dtype(band.dtype) != dt:
            raise TypeError(
                f"mixed band dtypes ({band.dtype} vs {dt}); cast first"
            )
        if tuple(band.shape) != lead + want:
            raise ValueError(
                f"malformed pyramid: band shape {tuple(band.shape)}, "
                f"geometry expects {lead + want}"
            )

    if ranges.checked_enabled(checked) and levels > 0:
        try:
            get_scheme(scheme)
        except ValueError:
            pass  # foreign scheme name: container records it, can't derive
        else:
            ranges.assert_encodable(
                bands, scheme=scheme, levels=levels, ndim=nd, mode=mode,
                label="codec.encode_pyramid",
            )

    scheme_b = scheme.encode("utf-8")
    if len(scheme_b) > 255:
        raise ValueError("scheme name too long")
    flags = 1 if (checksum and version == 1) else 0
    parts = [
        _HEAD.pack(
            MAGIC,
            version,
            kind,
            flags,
            _MODES[mode],
            _DTYPES[dt],
            levels,
            nd,
            len(lead),
            rice.BLOCK_VALUES,
            rice.Q_MAX,
            rice.K_MAX,
        ),
        bytes([len(scheme_b)]),
        scheme_b,
        struct.pack(f"<{len(lead)}I", *lead) if lead else b"",
        struct.pack(f"<{nd}I", *shape),
    ]
    blobs = []
    for band in bands:
        payload, ks, lens = rice.encode_band(band, backend=backend)
        blobs.append(ks.tobytes() + lens.astype("<u2").tobytes() + payload)
    parts.append(struct.pack(f"<{len(blobs)}I", *(len(b) for b in blobs)))
    if version == 1:
        parts.extend(blobs)
        out = b"".join(parts)
        if flags & 1:
            out += struct.pack("<I", zlib.crc32(out) & 0xFFFFFFFF)
        return out
    # v2: per-band CRCs, optional parity group, header CRC
    band_crcs = [zlib.crc32(b) & 0xFFFFFFFF for b in blobs]
    parts.append(struct.pack(f"<{len(band_crcs)}I", *band_crcs))
    parity_blob = b""
    parity_crc = 0
    if parity and blobs:
        parity_blob = _xor_parity(blobs, max(len(b) for b in blobs))
        parity_crc = zlib.crc32(parity_blob) & 0xFFFFFFFF
    parts.append(struct.pack("<II", len(parity_blob), parity_crc))
    header = b"".join(parts)
    header += struct.pack("<I", zlib.crc32(header) & 0xFFFFFFFF)
    return header + b"".join(blobs) + parity_blob


# ---------------------------------------------------------------------------
# Decode.
# ---------------------------------------------------------------------------


class _Header(NamedTuple):
    version: int
    kind: int
    flags: int
    mode: str
    dtype: np.dtype
    levels: int
    ndim: int
    scheme: str
    lead: Tuple[int, ...]
    shape: Tuple[int, ...]
    blob_lens: Tuple[int, ...]
    body_off: int  # offset of the first band blob
    band_crcs: Tuple[int, ...] = ()  # v2 only
    parity_len: int = 0  # v2 only
    parity_crc: int = 0  # v2 only


def _parse_header(data: bytes) -> _Header:
    if len(data) < _HEAD.size or data[:4] != MAGIC:
        raise CorruptHeaderError("not a WZRC container (bad magic)")
    try:
        return _parse_header_body(data)
    except (struct.error, IndexError) as e:
        # the variable-length tail ran past the buffer: corrupt counts or
        # a truncated blob — surface the module's documented error type
        raise CorruptHeaderError(
            f"truncated or corrupt WZRC header ({e})"
        ) from e


def _parse_header_body(data: bytes) -> _Header:
    (
        _,
        version,
        kind,
        flags,
        mode_c,
        dtype_c,
        levels,
        nd,
        nlead,
        block,
        qmax,
        kmax,
    ) = _HEAD.unpack_from(data, 0)
    if version not in SUPPORTED_VERSIONS:
        raise UnsupportedVersionError(
            f"WZRC container version {version} not supported by this build "
            f"(supports {SUPPORTED_VERSIONS})"
        )
    if (block, qmax, kmax) != (rice.BLOCK_VALUES, rice.Q_MAX, rice.K_MAX):
        raise CorruptHeaderError(
            f"container coded with rice geometry (block={block}, "
            f"qmax={qmax}, kmax={kmax}); this build uses "
            f"({rice.BLOCK_VALUES}, {rice.Q_MAX}, {rice.K_MAX})"
        )
    if kind not in (KIND_1D, KIND_2D, KIND_ND):
        raise CorruptHeaderError(f"unknown pyramid kind {kind}")
    if mode_c not in _MODE_NAMES or dtype_c not in _DTYPE_NAMES:
        raise CorruptHeaderError("corrupt container header (mode/dtype code)")
    off = _HEAD.size
    slen = data[off]
    off += 1
    scheme = data[off : off + slen].decode("utf-8", errors="replace")
    off += slen
    lead = struct.unpack_from(f"<{nlead}I", data, off)
    off += 4 * nlead
    shape = struct.unpack_from(f"<{nd}I", data, off)
    off += 4 * nd
    if kind == KIND_1D:
        nbands = 1 + levels
    elif kind == KIND_2D:
        nbands = 1 + 3 * levels
    else:
        nbands = 1 + ((1 << nd) - 1) * levels
    blob_lens = struct.unpack_from(f"<{nbands}I", data, off)
    off += 4 * nbands
    band_crcs: Tuple[int, ...] = ()
    parity_len = 0
    parity_crc = 0
    if version >= 2:
        band_crcs = struct.unpack_from(f"<{nbands}I", data, off)
        off += 4 * nbands
        parity_len, parity_crc = struct.unpack_from("<II", data, off)
        off += 8
        (want_crc,) = struct.unpack_from("<I", data, off)
        got_crc = zlib.crc32(data[:off]) & 0xFFFFFFFF
        off += 4
        if got_crc != want_crc:
            raise CorruptHeaderError(
                f"WZRC header checksum mismatch "
                f"(crc32 {got_crc:#010x} != {want_crc:#010x})"
            )
    return _Header(
        version=version,
        kind=kind,
        flags=flags,
        mode=_MODE_NAMES[mode_c],
        dtype=_DTYPE_NAMES[dtype_c],
        levels=levels,
        ndim=nd,
        scheme=scheme,
        lead=tuple(lead),
        shape=tuple(shape),
        blob_lens=tuple(blob_lens),
        body_off=off,
        band_crcs=band_crcs,
        parity_len=parity_len,
        parity_crc=parity_crc,
    )


def peek(data: bytes) -> dict:
    """Header metadata without decoding any band (cheap introspection)."""
    h = _parse_header(data)
    return {
        "version": h.version,
        "kind": h.kind,
        "scheme": h.scheme,
        "mode": h.mode,
        "levels": h.levels,
        "ndim": h.ndim,
        "lead": h.lead,
        "shape": h.shape,
        "dtype": str(h.dtype),
        "band_bytes": h.blob_lens,
        "parity_bytes": h.parity_len,
    }


def _decode_band_blob(
    blob: bytes, count: int
) -> np.ndarray:
    nb = rice.n_blocks(count)
    need = nb + 2 * nb
    if len(blob) < need:
        raise TruncatedStreamError(
            f"band blob truncated: {len(blob)} bytes, tables need {need}"
        )
    ks = np.frombuffer(blob, np.uint8, nb)
    lens = np.frombuffer(blob, "<u2", nb, offset=nb)
    return rice.decode_band(blob[nb + 2 * nb :], ks, lens, count)


def _band_blobs_v2(
    data: bytes, h: _Header
) -> Tuple[List[Optional[bytes]], List[str]]:
    """Slice out the band blobs, CRC-check each, heal via parity.

    Returns (blobs, status) in pack order; a blob is ``None`` exactly
    when its status is ``"corrupt"`` (CRC failed and parity could not
    reconstruct it).
    """
    end = len(data)
    if h.body_off + sum(h.blob_lens) + h.parity_len != end:
        raise TruncatedStreamError(
            f"container body is {end - h.body_off} bytes, band table sums "
            f"to {sum(h.blob_lens) + h.parity_len} (truncated or corrupt)"
        )
    blobs: List[Optional[bytes]] = []
    status: List[str] = []
    off = h.body_off
    for blen, crc in zip(h.blob_lens, h.band_crcs):
        blob = data[off : off + blen]
        off += blen
        if zlib.crc32(blob) & 0xFFFFFFFF == crc:
            blobs.append(blob)
            status.append(BAND_OK)
        else:
            blobs.append(None)
            status.append(BAND_CORRUPT)
    damaged = [i for i, s in enumerate(status) if s == BAND_CORRUPT]
    if damaged and h.parity_len:
        parity = data[off : off + h.parity_len]
        parity_ok = zlib.crc32(parity) & 0xFFFFFFFF == h.parity_crc
        if parity_ok and len(damaged) == 1:
            i = damaged[0]
            intact = [b for b in blobs if b is not None]
            rec = bytes(
                np.frombuffer(parity, np.uint8)
                ^ np.frombuffer(
                    _xor_parity(intact, h.parity_len), np.uint8
                )
            )[: h.blob_lens[i]]
            if zlib.crc32(rec) & 0xFFFFFFFF == h.band_crcs[i]:
                blobs[i] = rec
                status[i] = BAND_RECONSTRUCTED
    return blobs, status


def _assemble(h: _Header, bands: List[jax.Array]) -> Any:
    if h.kind == KIND_1D:
        return lifting.WaveletPyramid(approx=bands[0], details=tuple(bands[1:]))
    if h.kind == KIND_2D:
        details = tuple(
            (bands[1 + 3 * i], bands[2 + 3 * i], bands[3 + 3 * i])
            for i in range(h.levels)
        )
        return lifting.Pyramid2D(ll=bands[0], details=details)
    per = (1 << h.ndim) - 1
    details = tuple(
        tuple(bands[1 + per * i : 1 + per * (i + 1)])
        for i in range(h.levels)
    )
    return lifting.PyramidND(approx=bands[0], details=details)


def _decode_common(data: bytes, partial: bool):
    """Shared strict/partial decode core: header, bands, assembly."""
    data = bytes(data)
    h = _parse_header(data)
    end = len(data)
    if h.version == 1:
        if h.flags & 1:
            end -= 4
            (want,) = struct.unpack_from("<I", data, end)
            got = zlib.crc32(data[:end]) & 0xFFFFFFFF
            if got != want:
                raise CodecError(
                    f"WZRC checksum mismatch "
                    f"(crc32 {got:#010x} != {want:#010x})"
                )
        if h.body_off + sum(h.blob_lens) != end:
            raise TruncatedStreamError(
                f"container body is {end - h.body_off} bytes, band table "
                f"sums to {sum(h.blob_lens)} (truncated or corrupt)"
            )
        blobs: List[Optional[bytes]] = []
        off = h.body_off
        for blen in h.blob_lens:
            blobs.append(data[off : off + blen])
            off += blen
        status = [BAND_OK] * len(blobs)
    else:
        blobs, status = _band_blobs_v2(data, h)

    band_shapes = _expected_band_shapes(h.kind, h.shape, h.levels)
    lead_n = 1
    for s in h.lead:
        lead_n *= s
    bands = []
    for i, (blob, shp) in enumerate(zip(blobs, band_shapes)):
        count = lead_n
        for s in shp:
            count *= s
        if blob is not None:
            try:
                flat = _decode_band_blob(blob, count)
            except (CodecError, ValueError):
                # CRC-valid but undecodable should be impossible; treat
                # it as corruption rather than leaking a raw error
                blob = None
                status[i] = BAND_CORRUPT
        if blob is None:
            flat = np.zeros(count, np.int32)  # quarantined: shape-correct
        bands.append(
            jnp.asarray(flat.astype(h.dtype).reshape(h.lead + shp))
        )

    healed = sum(1 for s in status if s == BAND_RECONSTRUCTED)
    if healed:
        obs.counter("codec.bands_healed").inc(healed)
        obs.emit(obs.HealEvent(
            subsystem="codec", mechanism="parity",
            detail=f"{healed} band(s) reconstructed from the parity group",
        ))
    damaged = [i for i, s in enumerate(status) if s == BAND_CORRUPT]
    if damaged and not partial:
        obs.counter("codec.decode_corrupt").inc()
        obs.emit(obs.FaultEvent(
            subsystem="codec", error="CorruptBandError", site="codec.decode",
            detail=f"bands {damaged} unrecoverable",
        ))
        raise CorruptBandError(
            f"WZRC band(s) {damaged} corrupt and unrecoverable "
            f"({'parity absent' if not h.parity_len else 'parity could not heal'}); "
            "use decode_pyramid_partial for the surviving bands",
            band_status=status,
        )
    return h, _assemble(h, bands), tuple(status)


def _traced_decode(data: bytes, partial: bool):
    """Instrumented wrapper around :func:`_decode_common`: one span per
    container decode and its bytes into ``codec.decode_bytes``."""
    name = "codec.decode_pyramid_partial" if partial else "codec.decode_pyramid"
    with obs.span(name, subsystem="codec"):
        out = _decode_common(data, partial=partial)
    obs.counter("codec.decode_bytes").inc(len(data))
    return out


def decode_pyramid(data: bytes) -> DecodedPyramid:
    """Reconstruct the pyramid (and its self-description) from bytes.

    v2 blobs self-heal: a single damaged band reconstructs from the
    parity group when present (``band_status`` records it).  Damage
    that cannot heal raises :class:`CorruptBandError`; use
    :func:`decode_pyramid_partial` to recover the intact bands instead.
    """
    h, pyr, status = _traced_decode(data, partial=False)
    return DecodedPyramid(
        pyramid=pyr,
        kind=h.kind,
        scheme=h.scheme,
        mode=h.mode,
        levels=h.levels,
        lead=h.lead,
        shape=h.shape,
        dtype=h.dtype,
        band_status=status,
    )


def decode_pyramid_partial(data: bytes) -> PartialDecode:
    """Quarantining decode: return every recoverable band.

    Header damage still raises (:class:`CorruptHeaderError` — the
    geometry is unrecoverable), but band damage never does: corrupt
    bands come back zero-filled with ``band_status[i] == "corrupt"``
    and every other band is bit-exact.  v1 blobs carry no per-band
    CRCs, so for them this is equivalent to :func:`decode_pyramid`.
    """
    h, pyr, status = _traced_decode(data, partial=True)
    return PartialDecode(
        pyramid=pyr,
        kind=h.kind,
        scheme=h.scheme,
        mode=h.mode,
        levels=h.levels,
        lead=h.lead,
        shape=h.shape,
        dtype=h.dtype,
        band_status=status,
    )


def inverse_transform(dec, backend: Optional[str] = None):
    """Run the recorded inverse transform on a decoded pyramid.

    Convenience for sample-level consumers (ckpt, stream, serve): the
    container is self-describing, so the right engine (1D / 2D / N-D)
    and the recorded scheme/mode need no out-of-band metadata.  Accepts
    a :class:`DecodedPyramid` or a (complete) :class:`PartialDecode`.
    """
    from repro import kernels as K

    if dec.kind == KIND_1D:
        return K.dwt_inv(
            dec.pyramid, mode=dec.mode, backend=backend, scheme=dec.scheme
        )
    if dec.kind == KIND_2D:
        return K.dwt_inv_2d_multi(
            dec.pyramid, mode=dec.mode, backend=backend, scheme=dec.scheme
        )
    if dec.levels == 0:
        return dec.pyramid.approx  # identity pyramid carries no band order
    return K.dwt_inv_nd(
        dec.pyramid, mode=dec.mode, backend=backend, scheme=dec.scheme
    )


def encode_batch(
    pyr: Any,
    scheme: str = "cdf53",
    mode: str = "paper",
    *,
    ndim: Optional[int] = None,
    backend: Optional[str] = None,
    **kw,
) -> bytes:
    """Serialize a BATCH of pyramids as one container (lead dim = batch).

    The WZRC layout has always carried leading (batch) dims; this entry
    point is the serve tier's contract for it: the pyramid's bands must
    have at least one leading dim, which is the micro-batch.  One
    container per micro-batch amortizes the host-side Rice coder over
    the batch — every band is coded in ONE pass over ``(B, ...)`` data
    instead of B per-request passes (the serve bench gates the ratio).

    Decode the whole batch with :func:`decode_batch`, or any single
    band/tier of it with ``codec.progressive`` (the per-band byte
    ranges serve the batch container exactly like a single-request one;
    each band decodes to ``(B, ...)``).
    """
    kind = _pyramid_kind(pyr)
    nd, lead, _ = _infer_geometry(pyr, kind, ndim)
    if not lead:
        raise ValueError(
            "encode_batch needs a leading batch dim on every band; got a "
            f"lead-free pyramid (trailing ndim={nd}) — use encode_pyramid "
            "for single requests"
        )
    return encode_pyramid(
        pyr, scheme, mode, ndim=ndim, backend=backend, **kw
    )


def decode_batch(data: bytes) -> List[Any]:
    """Split a batch container back into per-item pyramids.

    The inverse of :func:`encode_batch`: decodes once (self-healing and
    typed errors exactly as :func:`decode_pyramid`) and slices the
    leading batch dim, returning one pyramid per batch row.  Raises
    ``ValueError`` on a container with no lead dims.
    """
    dec = decode_pyramid(data)
    if not dec.lead:
        raise ValueError(
            "not a batch container (no lead dims); use decode_pyramid"
        )
    return [
        jax.tree_util.tree_map(lambda b, i=i: b[i], dec.pyramid)
        for i in range(dec.lead[0])
    ]


def roundtrip_exact(pyr: Any, **kw) -> bool:
    """True when encode->decode reproduces every band bit-exactly."""
    dec = decode_pyramid(encode_pyramid(pyr, **kw))
    got = jax.tree_util.tree_leaves(dec.pyramid)
    want = jax.tree_util.tree_leaves(pyr)
    return len(got) == len(want) and all(
        a.shape == np.asarray(b).shape and bool(np.array_equal(a, b))
        for a, b in zip(map(np.asarray, got), map(np.asarray, want))
    )
