"""Roundtrip and adversarial sweeps for the entropy codec (repro.codec).

The codec's contract is BIT-EXACTNESS from bytes alone: every registered
scheme x 1D/2D/3D pyramid x both rounding modes must decode to the exact
bands that were encoded, on adversarial inputs too — constant bands,
uniform noise, max-magnitude int32 coefficients (the Rice escape path),
and the degenerate shapes of test_degenerate.py.  Consumer wiring (ckpt
``wz-rice``, measured ``encoded_bytes_*``, ``pod_encoded_bytes``, the
serve encoded-response route, the stream layer) is covered here as well.
"""
import hashlib
import io
import json
import zlib
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import kernels as K
from repro.codec import container, rice, stream
from repro.core import lifting as L

RNG = np.random.default_rng(11)

SCHEMES = ("cdf53", "haar", "cdf22", "97m")
MODES = ("paper", "jpeg2000")

I32_MIN, I32_MAX = -(2**31), 2**31 - 1


# ---------------------------------------------------------------------------
# Rice primitive: flat-band encode/decode.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "vals",
    [
        np.zeros(1000, np.int32),  # constant: k=0 degenerate blocks
        np.full(513, 7, np.int32),
        np.full(300, I32_MIN, np.int32),  # every code escapes
        np.full(300, I32_MAX, np.int32),
        np.array([0], np.int32),
        np.array([], np.int32),  # empty band
        np.arange(-640, 640, dtype=np.int32),
    ],
)
def test_rice_band_adversarial_roundtrip(vals):
    payload, ks, lens = rice.encode_band(vals)
    out = rice.decode_band(payload, ks, lens, vals.size)
    np.testing.assert_array_equal(out, vals)


def test_rice_multi_chunk_roundtrip():
    """Bands larger than one compiled chunk must stitch exactly."""
    x = RNG.integers(-3000, 3000, rice.CHUNK_BLOCKS * rice.BLOCK_VALUES + 777)
    x = x.astype(np.int32)
    payload, ks, lens = rice.encode_band(x)
    np.testing.assert_array_equal(
        rice.decode_band(payload, ks, lens, x.size), x
    )


def test_rice_compresses_small_magnitudes():
    x = RNG.integers(-8, 8, 4096).astype(np.int32)
    payload, _, _ = rice.encode_band(x)
    assert len(payload) < x.size  # well under 1 byte/value, vs 4 raw


def test_rice_backend_parity():
    """The Pallas (interpret off-accelerator) and XLA bit-pack paths must
    produce identical streams."""
    x = RNG.integers(-500, 500, 2000).astype(np.int32)
    p_xla, k_xla, l_xla = rice.encode_band(x, backend="xla")
    p_int, k_int, l_int = rice.encode_band(x, backend="pallas")
    assert p_xla == p_int
    np.testing.assert_array_equal(k_xla, k_int)
    np.testing.assert_array_equal(l_xla, l_int)


def test_rice_zigzag_involution():
    x = jnp.asarray(
        [0, -1, 1, 17, -17, I32_MIN, I32_MAX, 12345, -12345], jnp.int32
    )
    np.testing.assert_array_equal(
        np.asarray(rice.unzigzag(rice.zigzag(x))), np.asarray(x)
    )


def test_rice_truncated_payload_rejected():
    x = RNG.integers(-500, 500, 1000).astype(np.int32)
    payload, ks, lens = rice.encode_band(x)
    with pytest.raises(ValueError, match="truncated or corrupt"):
        rice.decode_band(payload[:-3], ks, lens, x.size)


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=900),
    lo=st.sampled_from([-4, -1000, I32_MIN]),
    hi=st.sampled_from([5, 1000, I32_MAX]),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_rice_roundtrip(n, lo, hi, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(lo, int(hi) + 1, n, dtype=np.int64).astype(np.int32)
    payload, ks, lens = rice.encode_band(x)
    np.testing.assert_array_equal(
        rice.decode_band(payload, ks, lens, n), x
    )


# ---------------------------------------------------------------------------
# Rice bytes against a sequential bit writer built from the format alone.
# ---------------------------------------------------------------------------


def _sequential_rice(x):
    """The coded stream of ``x``, one bit at a time, from the documented
    format: blocks of ``BLOCK_VALUES`` (the last zero-padded), zigzag,
    the ``k`` of least total length (the smallest on a tie), then per
    value ``q = u >> k`` ones, a 0 and the ``k`` low bits of ``u``, or
    ``Q_MAX`` ones and all 32 bits of ``u`` where ``q >= Q_MAX``; each
    block MSB-first, padded to whole bytes.  Returns ``(payload, k_table,
    byte_lengths, code_offsets)``."""
    flat = np.asarray(x, np.int64).reshape(-1)
    nb = -(-flat.size // rice.BLOCK_VALUES)
    blocks = np.zeros(nb * rice.BLOCK_VALUES, np.int64)
    blocks[: flat.size] = flat
    u_all = ((blocks << 1) ^ (blocks >> 63)) & 0xFFFFFFFF
    payload, ks, blens, offsets = [], [], [], []
    for u in u_all.reshape(nb, rice.BLOCK_VALUES):
        cost = [
            np.where((u >> k) >= rice.Q_MAX, rice.Q_MAX + 32, (u >> k) + 1 + k).sum()
            for k in range(rice.K_MAX + 1)
        ]
        k = int(np.argmin(cost))
        bits = []
        for v in u.tolist():
            offsets.append(sum(map(len, bits)))
            q = v >> k
            if q >= rice.Q_MAX:
                bits.append("1" * rice.Q_MAX + format(v, "032b"))
            else:
                bits.append("1" * q + "0" + (format(v & ((1 << k) - 1), f"0{k}b") if k else ""))
        s = "".join(bits)
        s += "0" * (-len(s) % 8)
        payload.append(int(s, 2).to_bytes(len(s) // 8, "big") if s else b"")
        ks.append(k)
        blens.append(len(s) // 8)
    return b"".join(payload), np.array(ks), np.array(blens), np.array(offsets)


def _golden_cases():
    rng = np.random.default_rng(1515)
    cases = {}
    for nb in (1, 2, 4, 8, 16, 32, 64, 128):  # every chunk bucket
        n = nb * rice.BLOCK_VALUES - 3
        cases[f"bucket{nb}"] = rng.laplace(0, 4.0 * nb, n)
    cases["all_escape"] = rng.integers(2**29, 2**31, 768) * rng.choice([-1, 1], 768)
    cases["int32_extremes"] = np.tile([I32_MIN, I32_MAX, 0, -1, I32_MIN + 1], 120)
    cases["k0"] = rng.integers(-1, 1, 600)
    cases["k24"] = rng.integers(2**25, 2**26, 768) * rng.choice([-1, 1], 768)
    # 32 escapes each followed by one 1-bit zero code: the 41-bit strides
    # start an escape at every bit offset mod 32
    esc = np.zeros(rice.BLOCK_VALUES, np.int64)
    esc[0:64:2] = I32_MAX
    cases["escape_every_offset"] = esc
    cases["mixed_every_offset"] = np.concatenate(
        [rng.laplace(0, 2.0 ** rng.integers(0, 22), 64) for _ in range(12)]
    )
    # one all-escape block of BYTES_CAP bytes among narrow ones: the chunk's
    # byte bucket is the widest, 2048, and its lanes end words apart
    cap = rng.laplace(0, 3.0, 16 * rice.BLOCK_VALUES)
    cap[5 * rice.BLOCK_VALUES : 6 * rice.BLOCK_VALUES] = I32_MIN
    cases["byte_cap"] = cap
    return {
        k: np.clip(np.rint(v), I32_MIN, I32_MAX).astype(np.int32)
        for k, v in cases.items()
    }


GOLDEN_CASES = _golden_cases()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(GOLDEN_CASES))
def test_rice_matches_sequential_writer(case, backend):
    x = GOLDEN_CASES[case]
    want, want_k, want_len, offsets = _sequential_rice(x)
    # the cases cover what they are named for
    if case == "k0":
        assert (want_k == 0).all()
    if case == "k24":
        assert (want_k == rice.K_MAX).all()
    if case == "all_escape":
        assert len(want) == 5 * x.size  # every code 40 bits
    if case.endswith("every_offset"):
        assert set((offsets % 32).tolist()) == set(range(32))
    if case == "byte_cap":
        assert want_len.max() == rice.BYTES_CAP
    payload, ks, lens = rice.encode_band(x, backend=backend)
    assert payload == want
    np.testing.assert_array_equal(ks, want_k)
    np.testing.assert_array_equal(lens, want_len)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(GOLDEN_CASES))
def test_rice_decodes_sequential_writer(case, backend):
    """Each golden case, as the sequential writer codes it, decodes to
    itself on every unpack path."""
    x = GOLDEN_CASES[case]
    payload, ks, lens, _ = _sequential_rice(x)
    got = rice.decode_band(payload, ks, lens, x.size, backend=backend)
    np.testing.assert_array_equal(got, x)


def _digest_band():
    rng = np.random.default_rng(20150)
    n = 2 * rice.CHUNK_BLOCKS * rice.BLOCK_VALUES + 777
    x = rng.laplace(0, 300, n).astype(np.int64)
    idx = rng.integers(0, n, 400)
    x[idx] = rng.integers(-(2**31), 2**31, 400)
    x[:5] = [I32_MIN, I32_MAX, 0, -1, 1]
    return np.clip(x, I32_MIN, I32_MAX).astype(np.int32)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_rice_frozen_payload_digest(backend):
    """A seeded band over three chunks codes to the bytes the scatter-
    and-bit-plane encoder wrote before the shift-or pack replaced it."""
    payload, ks, lens = rice.encode_band(_digest_band(), backend=backend)
    assert len(payload) == 90849
    assert hashlib.sha256(payload).hexdigest() == (
        "88b3dee0a36aea81eb794c47471af3b0b957ee379b2e37c75d35b0d61aca5363"
    )
    assert hashlib.sha256(ks.tobytes() + lens.tobytes()).hexdigest() == (
        "31ca00e556c29ecc6b6fa0a30b821627113e5fdf98add72e4a819dbb0fc4891c"
    )


def test_rice_digest_band_decodes_alike_on_every_path():
    """The frozen-digest band, three chunks with escapes among them,
    decodes to itself on the XLA scan and on the Pallas kernel alike."""
    x = _digest_band()
    payload, ks, lens = rice.encode_band(x)
    xla = rice.decode_band(payload, ks, lens, x.size, backend="xla")
    pallas = rice.decode_band(payload, ks, lens, x.size, backend="pallas")
    np.testing.assert_array_equal(xla, x)
    np.testing.assert_array_equal(pallas, xla)


# ---------------------------------------------------------------------------
# Container: every scheme x dimensionality x mode, bit-exact from bytes.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("name", SCHEMES)
def test_container_1d_roundtrip_every_scheme(name, mode):
    x = jnp.asarray(RNG.integers(-4096, 4096, (3, 41)), jnp.int32)
    pyr = K.dwt_fwd(x, levels=3, mode=mode, scheme=name)
    assert container.roundtrip_exact(pyr, scheme=name, mode=mode)
    dec = container.decode_pyramid(
        container.encode_pyramid(pyr, scheme=name, mode=mode)
    )
    assert dec.scheme == name and dec.mode == mode and dec.shape == (41,)
    np.testing.assert_array_equal(
        np.asarray(container.inverse_transform(dec)), np.asarray(x)
    )


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("name", SCHEMES)
def test_container_2d_roundtrip_every_scheme(name, mode):
    x = jnp.asarray(RNG.integers(-4096, 4096, (2, 19, 23)), jnp.int32)
    pyr = K.dwt_fwd_2d_multi(x, levels=2, mode=mode, scheme=name)
    assert container.roundtrip_exact(pyr, scheme=name, mode=mode)
    dec = container.decode_pyramid(
        container.encode_pyramid(pyr, scheme=name, mode=mode)
    )
    assert dec.lead == (2,) and dec.shape == (19, 23)
    np.testing.assert_array_equal(
        np.asarray(container.inverse_transform(dec)), np.asarray(x)
    )


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("name", SCHEMES)
def test_container_3d_roundtrip_every_scheme(name, mode):
    x = jnp.asarray(RNG.integers(-4096, 4096, (6, 9, 10)), jnp.int32)
    pyr = K.dwt_fwd_nd(x, levels=2, mode=mode, scheme=name, ndim=3)
    assert container.roundtrip_exact(pyr, scheme=name, mode=mode)
    dec = container.decode_pyramid(
        container.encode_pyramid(pyr, scheme=name, mode=mode)
    )
    np.testing.assert_array_equal(
        np.asarray(container.inverse_transform(dec)), np.asarray(x)
    )


@pytest.mark.parametrize(
    "shape", [(1, 2), (2, 2), (1, 3), (4, 2, 3), (1, 2, 2, 2)]
)
def test_container_degenerate_shapes(shape):
    """The tiny/odd shapes of test_degenerate.py through the codec."""
    x = jnp.asarray(RNG.integers(-500, 500, shape), jnp.int32)
    levels = L.max_levels(shape[-1])
    pyr = K.dwt_fwd(x, levels=levels)
    assert container.roundtrip_exact(pyr)


def test_container_levels_zero_identity():
    x = jnp.asarray(RNG.integers(0, 9, (4, 4, 4)), jnp.int32)
    pyr = L.dwt_fwd_nd(x, levels=0, ndim=3)
    dec = container.decode_pyramid(container.encode_pyramid(pyr, ndim=3))
    np.testing.assert_array_equal(
        np.asarray(container.inverse_transform(dec)), np.asarray(x)
    )
    with pytest.raises(ValueError, match="ndim"):
        container.encode_pyramid(pyr)  # levels=0 ND needs the hint


def test_container_extreme_band_values():
    """Max-magnitude int32 coefficients ride the Rice escape path."""
    pyr = L.WaveletPyramid(
        approx=jnp.asarray([[I32_MIN, I32_MAX, 0, -1]], jnp.int32),
        details=(jnp.asarray([[I32_MAX, I32_MIN, 1]], jnp.int32),),
    )
    assert container.roundtrip_exact(pyr)


def test_container_constant_bands_compress():
    x = jnp.full((64, 64), 123, jnp.int32)
    pyr = K.dwt_fwd_2d_multi(x, levels=2)
    blob = container.encode_pyramid(pyr)
    assert container.roundtrip_exact(pyr)
    assert len(blob) < x.size  # constant image: way under 1 byte/sample


def test_container_narrow_dtypes_roundtrip():
    """int8/int16 band payloads keep their dtype through the container."""
    for dt in (jnp.int8, jnp.int16):
        pyr = L.WaveletPyramid(
            approx=jnp.asarray([[1, -2, 3]], dt),
            details=(jnp.asarray([[4, -5]], dt),),
        )
        dec = container.decode_pyramid(container.encode_pyramid(pyr))
        assert dec.pyramid.approx.dtype == dt
        assert container.roundtrip_exact(pyr)


def test_container_rejects_corruption_and_unknown_version():
    pyr = K.dwt_fwd(jnp.asarray(RNG.integers(0, 99, (1, 32)), jnp.int32), 2)
    blob = bytearray(container.encode_pyramid(pyr))
    flipped = bytearray(blob)
    flipped[len(flipped) // 2] ^= 0xFF
    with pytest.raises(ValueError, match="checksum|corrupt|truncated"):
        container.decode_pyramid(bytes(flipped))
    with pytest.raises(ValueError, match="magic"):
        container.decode_pyramid(b"JUNK" + bytes(blob[4:]))
    versioned = bytearray(blob)
    versioned[4] = 99  # future format version
    with pytest.raises(ValueError, match="version 99"):
        container.decode_pyramid(bytes(versioned))


def test_container_rejects_malformed_pyramid():
    x = jnp.asarray(RNG.integers(0, 99, (1, 32)), jnp.int32)
    pyr = K.dwt_fwd(x, levels=2)
    bad = L.WaveletPyramid(
        approx=pyr.approx, details=(pyr.details[0][..., :-1],) + pyr.details[1:]
    )
    with pytest.raises(ValueError, match="malformed pyramid"):
        container.encode_pyramid(bad)
    with pytest.raises(TypeError):
        container.encode_pyramid(
            L.WaveletPyramid(
                approx=pyr.approx.astype(jnp.float32), details=pyr.details
            )
        )


def test_container_peek_matches_decode():
    x = jnp.asarray(RNG.integers(-99, 99, (2, 8, 12)), jnp.int32)
    pyr = K.dwt_fwd_2d_multi(x, levels=2, scheme="97m")
    blob = container.encode_pyramid(pyr, scheme="97m", mode="jpeg2000")
    meta = container.peek(blob)
    assert meta["scheme"] == "97m" and meta["mode"] == "jpeg2000"
    assert meta["lead"] == (2,) and meta["shape"] == (8, 12)
    assert sum(meta["band_bytes"]) > 0


@settings(max_examples=25, deadline=None)
@given(
    name=st.sampled_from(SCHEMES),
    mode=st.sampled_from(MODES),
    ndim=st.integers(min_value=1, max_value=3),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_any_scheme_any_ndim_roundtrip(name, mode, ndim, seed):
    rng = np.random.default_rng(seed)
    dims = tuple(int(d) for d in rng.integers(4, 12, ndim))
    x = jnp.asarray(rng.integers(-(2**14), 2**14, (2,) + dims), jnp.int32)
    levels = min(2, L.max_levels_nd(dims))
    if ndim == 1:
        pyr = K.dwt_fwd(x, levels=levels, mode=mode, scheme=name)
    elif ndim == 2:
        pyr = K.dwt_fwd_2d_multi(x, levels=levels, mode=mode, scheme=name)
    else:
        pyr = K.dwt_fwd_nd(x, levels=levels, mode=mode, scheme=name, ndim=3)
    assert container.roundtrip_exact(pyr, scheme=name, mode=mode)


# ---------------------------------------------------------------------------
# Stream layer.
# ---------------------------------------------------------------------------


def test_stream_volume_roundtrip_partial_final_slab():
    vol = RNG.integers(-2000, 2000, (19, 16, 12)).astype(np.int32)
    blobs = b"".join(stream.encode_volume(vol, slab=8, levels=2))
    np.testing.assert_array_equal(stream.decode_volume(blobs), vol)


def test_stream_sources_bytes_file_iterable():
    vol = RNG.integers(-99, 99, (6, 8, 8)).astype(np.int32)
    data = b"".join(stream.encode_volume(vol, slab=4, levels=1, scheme="haar"))
    np.testing.assert_array_equal(stream.decode_volume(data), vol)
    np.testing.assert_array_equal(
        stream.decode_volume(io.BytesIO(data)), vol
    )
    pieces = [data[i : i + 37] for i in range(0, len(data), 37)]
    np.testing.assert_array_equal(stream.decode_volume(iter(pieces)), vol)


def test_stream_frames_never_hold_whole_volume():
    """Frame sizes stay slab-bounded: the streaming property."""
    vol = RNG.integers(-500, 500, (32, 16, 16)).astype(np.int32)
    frames = list(stream.encode_volume(vol, slab=4, levels=1))
    # header + 8 frames + terminator; every frame well under the volume
    assert len(frames) == 10
    whole = b"".join(stream.encode_volume(vol, slab=32, levels=1))
    assert all(len(f) < len(whole) // 2 for f in frames[1:-1])


def test_stream_truncation_and_bad_magic_rejected():
    vol = RNG.integers(-99, 99, (4, 8, 8)).astype(np.int32)
    data = b"".join(stream.encode_volume(vol, slab=2, levels=1))
    with pytest.raises(ValueError, match="truncated"):
        list(stream.decode_stream(data[:-6]))
    with pytest.raises(ValueError, match="magic"):
        list(stream.decode_stream(b"XXXX" + data[4:]))


def test_stream_short_read_file_source():
    """Unbuffered file-likes may legally return fewer bytes than asked;
    the reader must loop, not misreport a valid stream as truncated."""

    class DribbleReader(io.RawIOBase):
        def __init__(self, data):
            self._data, self._pos = data, 0

        def readable(self):
            return True

        def read(self, n=-1):
            if self._pos >= len(self._data):
                return b""
            chunk = self._data[self._pos : self._pos + min(7, n)]
            self._pos += len(chunk)
            return chunk

    vol = RNG.integers(-99, 99, (4, 8, 8)).astype(np.int32)
    data = b"".join(stream.encode_volume(vol, slab=2, levels=1))
    np.testing.assert_array_equal(
        stream.decode_volume(DribbleReader(data)), vol
    )


def test_container_truncated_header_raises_value_error():
    """Cutting a blob mid-header must raise the documented ValueError,
    never a raw struct.error, so `except ValueError` callers stay safe."""
    x = jnp.asarray(RNG.integers(0, 99, (1, 32)), jnp.int32)
    blob = container.encode_pyramid(K.dwt_fwd(x, levels=2))
    for cut in (15, 17, 20, 24):
        with pytest.raises(ValueError):
            container.decode_pyramid(blob[:cut])


def test_stream_encoder_rejects_float_chunks():
    enc = stream.StreamEncoder(levels=1, ndim=2)
    with pytest.raises(TypeError, match="integer"):
        enc.encode_frame(np.ones((8, 8), np.float32))


# ---------------------------------------------------------------------------
# Consumers.
# ---------------------------------------------------------------------------


def test_ckpt_wz_rice_roundtrip_and_manifest(tmp_path):
    from repro.ckpt.checkpoint import CheckpointManager

    tree = {
        "conv": np.asarray(RNG.normal(size=(6, 8, 8)), np.float32),
        "mat": np.asarray(RNG.normal(size=(24, 16)), np.float32),
        "vec": np.asarray(RNG.normal(size=(48,)), np.float32),
        "s": np.float32(1.25),
    }
    mgr = CheckpointManager(tmp_path, codec="wz-rice", wavelet_levels=2)
    mgr.save(1, tree)
    _, out = mgr.restore(template=tree)
    for k in ("conv", "mat", "vec"):
        amax = np.max(np.abs(tree[k]))
        # full int16 quantization: error <= scale/2 at ANY depth (no
        # 32767 >> levels headroom shift, unlike the zlib wz family)
        assert np.max(np.abs(out[k] - tree[k])) <= amax / 32767 * 0.51, k
    manifest = json.loads(
        (Path(tmp_path) / "step_0000000001" / "manifest.json").read_text()
    )
    metas = {k: m["meta"] for k, m in manifest["leaves"].items()}
    assert {k: m["enc"] for k, m in metas.items()} == {
        "conv": "3d", "mat": "2d", "vec": "1d", "s": "1d",
    }
    # wz-rice leaves ride the self-healing WZRC v2 container (per-band
    # CRCs + parity); the zlib wz family stays enc_version 1
    assert all(m["enc_version"] == 2 for m in metas.values())
    assert all(m["parity"] is True for m in metas.values())


def test_ckpt_enc_version_recorded_for_all_wavelet_codecs(tmp_path):
    from repro.ckpt.checkpoint import CheckpointManager

    tree = {"w": np.asarray(RNG.normal(size=(16, 16)), np.float32)}
    for codec in ("wz", "wz2d", "wz3d", "wz-rice"):
        mgr = CheckpointManager(
            tmp_path / codec, codec=codec, wavelet_levels=2
        )
        mgr.save(1, tree)
        manifest = json.loads(
            (Path(tmp_path) / codec / "step_0000000001" / "manifest.json")
            .read_text()
        )
        # wz-rice writes the v2 container; the zlib family stays v1 so
        # old builds keep reading unchanged payloads
        want = 2 if codec == "wz-rice" else 1
        assert manifest["leaves"]["w"]["meta"]["enc_version"] == want, codec


def test_ckpt_unknown_enc_version_rejected(tmp_path):
    from repro.ckpt.checkpoint import CheckpointManager

    tree = {"w": np.asarray(RNG.normal(size=(16, 16)), np.float32)}
    mgr = CheckpointManager(tmp_path, codec="wz-rice", wavelet_levels=2)
    mgr.save(1, tree)
    mpath = Path(tmp_path) / "step_0000000001" / "manifest.json"
    manifest = json.loads(mpath.read_text())
    manifest["leaves"]["w"]["meta"]["enc_version"] = 99
    mpath.write_text(json.dumps(manifest))
    with pytest.raises(ValueError, match="enc_version 99"):
        mgr.restore(1, template=tree)


def test_ckpt_legacy_manifest_without_enc_version_restores(tmp_path):
    """Pre-enc_version manifests carry version-1 payloads; they must
    keep restoring (missing field == 1), only UNKNOWN versions fail."""
    from repro.ckpt.checkpoint import CheckpointManager

    tree = {"w": np.asarray(RNG.normal(size=(16, 16)), np.float32)}
    mgr = CheckpointManager(tmp_path, codec="wz", wavelet_levels=2)
    mgr.save(1, tree)
    mpath = Path(tmp_path) / "step_0000000001" / "manifest.json"
    manifest = json.loads(mpath.read_text())
    del manifest["leaves"]["w"]["meta"]["enc_version"]
    mpath.write_text(json.dumps(manifest))
    _, out = mgr.restore(1, template=tree)
    assert np.max(np.abs(out["w"] - tree["w"])) < 0.05


def test_ckpt_wz_rice_beats_plain_zlib_on_smooth(tmp_path):
    """The acceptance claim, pinned in tier-1: smooth checkpoint-like
    tensors store smaller under wz-rice than under plain zlib."""
    from repro.ckpt.checkpoint import _encode

    yy, xx = np.meshgrid(
        np.linspace(0, 2, 128), np.linspace(0, 2, 96), indexing="ij"
    )
    smooth = (np.sin(yy + xx) + 0.01 * RNG.normal(size=yy.shape)).astype(
        np.float32
    )
    rice_b, meta = _encode(smooth, "wz-rice", 2)
    z_b, _ = _encode(smooth, "z", 2)
    assert len(rice_b) < len(z_b)
    assert len(rice_b) < len(zlib.compress(smooth.tobytes(), 9))


def test_compression_encoded_bytes_measured_vs_analytic():
    from repro.core import compression as C

    yy, xx = np.meshgrid(
        np.linspace(0, 3, 64), np.linspace(0, 3, 48), indexing="ij"
    )
    g = jnp.asarray(np.sin(yy) * np.cos(xx), jnp.float32)
    e1 = C.encoded_bytes(g, 2)
    e2 = C.encoded_bytes_2d(g, 2)
    assert 0 < e2 < g.size * 4 and 0 < e1 < g.size * 4
    assert C.encoded_ratio_2d(g, 2) > 1.0
    # the analytic estimate answers a different question (raw payload
    # geometry); both exist, named apart
    assert C.band_bytes_2d(64, 48, 2) != e2


def test_compression_encoded_bytes_nd():
    from repro.core import compression as C

    t = np.linspace(0, 2, 12)
    g = jnp.asarray(
        np.sin(t)[:, None, None]
        * np.cos(t)[None, :, None]
        * np.sin(t + 1)[None, None, :],
        jnp.float32,
    )
    e3 = C.encoded_bytes_nd(g, 1, ndim=3)
    assert 0 < e3 < g.size * 4
    assert C.encoded_ratio_nd(g, 1) > 1.0


def test_grad_pod_encoded_bytes():
    from repro.core import compression as C
    from repro.train.grad_compress import (
        WaveletSyncConfig,
        pod_collective_bytes,
        pod_encoded_bytes,
    )

    yy, xx = np.meshgrid(
        np.linspace(0, 3, 96), np.linspace(0, 3, 64), indexing="ij"
    )
    grads = {
        "smooth": jnp.asarray(np.sin(yy + xx), jnp.float32),
        "tiny": jnp.asarray(RNG.normal(size=(10,)), jnp.float32),
    }
    cfg = WaveletSyncConfig(levels=2, min_size=64, spatial_2d=True)
    raw, enc = pod_encoded_bytes(grads, cfg)
    raw_a, _ = pod_collective_bytes(grads, cfg)
    assert raw == raw_a  # same fp32 baseline
    assert enc < raw  # measured coded bytes beat fp32
    # tiny leaf syncs uncompressed in both accountings
    assert enc >= 10 * 4


def test_serve_encoded_response_roundtrip():
    from repro.serve.serve_step import TransformRequest, WaveletServeEngine

    eng = WaveletServeEngine(
        height=16, width=16, batch_slots=2, levels=2, scheme="97m",
        encode_response=True,
    )
    reqs = [
        TransformRequest(
            uid=i, image=RNG.integers(-500, 500, (16, 16)).astype(np.int32)
        )
        for i in range(3)
    ]
    for r in eng.run(reqs):
        # responses share ONE WZRC container per micro-batch; each
        # request carries its row index into the batched decode
        dec = container.decode_pyramid(r.encoded)
        assert dec.scheme == "97m"
        row = container.decode_batch(r.encoded)[r.batch_index]
        np.testing.assert_array_equal(
            np.asarray(
                container.inverse_transform(dec._replace(pyramid=row, lead=()))
            ),
            r.image,
        )


def test_serve_encoded_response_volume():
    from repro.serve.serve_step import TransformRequest, WaveletServeEngine

    eng = WaveletServeEngine(
        height=8, width=8, depth=8, batch_slots=1, levels=1,
        encode_response=True,
    )
    req = TransformRequest(
        uid=0, image=RNG.integers(-500, 500, (8, 8, 8)).astype(np.int32)
    )
    eng.run([req])
    dec = container.decode_pyramid(req.encoded)
    row = container.decode_batch(req.encoded)[req.batch_index]
    np.testing.assert_array_equal(
        np.asarray(
            container.inverse_transform(dec._replace(pyramid=row, lead=()))
        ),
        req.image,
    )


def test_serve_encode_response_off_by_default():
    from repro.serve.serve_step import TransformRequest, WaveletServeEngine

    eng = WaveletServeEngine(height=8, width=8, batch_slots=1, levels=1)
    req = TransformRequest(
        uid=0, image=RNG.integers(0, 99, (8, 8)).astype(np.int32)
    )
    eng.run([req])
    assert req.encoded is None and req.pyramid is not None
