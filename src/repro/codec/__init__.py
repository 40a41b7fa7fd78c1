"""Lossless entropy-coded bitstream codec over the integer wavelet bands.

The back half of the paper's lossless filter bank: the multiplierless
integer DWT (``repro.kernels``) concentrates energy; this package turns
the resulting pyramids into compact, self-describing bytes and back,
bit-exactly.

    rice.py       adaptive Golomb-Rice coder — zigzag mapping, per-block
                  shift-add optimal ``k`` selection on device, per-value
                  shift-or packing into 32-bit words with a Pallas pack
                  kernel under the ``kernels/backend.py`` dispatch policy
    container.py  one pyramid -> one self-describing blob (magic/version,
                  kind/scheme/mode/levels/shape/dtype, per-band k tables
                  and byte offsets, crc32) — round-trips any
                  WaveletPyramid / Pyramid2D / PyramidND from bytes alone;
                  ``encode_batch``/``decode_batch`` treat the lead dim as
                  a serve micro-batch (one container per batch)
    progressive.py byte-range decode of one stored container into fidelity
                  tiers — ``decode_lowband`` (thumbnail), ``decode_band``
                  (any band), ``decode_progressive`` (coarsest L levels),
                  each reading only the byte ranges it returns,
                  CRC-checked per band and parity-aware
    stream.py     framed sequences of containers for chunked / streaming
                  encode-decode (volumes per depth-slab on the serve path)

Consumers: ``ckpt/checkpoint.py`` (the ``wz-rice`` leaf codec),
``core/compression.py`` (``encoded_bytes_*`` / ``encoded_ratio_*``
measured wire sizes), ``train/grad_compress.py``
(``pod_encoded_bytes``), ``serve/`` (batch-encoded responses +
progressive fidelity-tier routes).  See DESIGN.md §11 and §14.

``decode_band`` at this package level is the PROGRESSIVE per-band
decoder (container in, one band out); the coder-level primitive of the
same name stays at ``repro.codec.rice.decode_band``.
"""
from repro.codec.container import (  # noqa: F401
    DecodedPyramid,
    PartialDecode,
    decode_batch,
    decode_pyramid,
    decode_pyramid_partial,
    encode_batch,
    encode_pyramid,
    inverse_transform,
    peek,
    roundtrip_exact,
)
from repro.codec.errors import (  # noqa: F401
    CodecError,
    CorruptBandError,
    CorruptHeaderError,
    TruncatedStreamError,
    UnsupportedVersionError,
)
from repro.codec.progressive import (  # noqa: F401
    BandDecode,
    CountingReader,
    decode_band,
    decode_lowband,
    decode_progressive,
    read_header,
    reconstruct,
)
from repro.codec.rice import (  # noqa: F401
    BLOCK_VALUES,
    encode_band,
    unzigzag,
    zigzag,
)
from repro.codec.stream import (  # noqa: F401
    StreamEncoder,
    decode_stream,
    decode_volume,
    encode_volume,
    iter_frames,
)

__all__ = [
    "CodecError",
    "CorruptBandError",
    "CorruptHeaderError",
    "TruncatedStreamError",
    "UnsupportedVersionError",
    "DecodedPyramid",
    "PartialDecode",
    "decode_batch",
    "decode_pyramid",
    "decode_pyramid_partial",
    "encode_batch",
    "encode_pyramid",
    "inverse_transform",
    "peek",
    "roundtrip_exact",
    "BandDecode",
    "CountingReader",
    "decode_band",
    "decode_lowband",
    "decode_progressive",
    "read_header",
    "reconstruct",
    "BLOCK_VALUES",
    "encode_band",
    "unzigzag",
    "zigzag",
    "StreamEncoder",
    "decode_stream",
    "decode_volume",
    "encode_volume",
    "iter_frames",
]
