"""Compile-only guards: the main-path kernels for a described TPU v5e.

Interpret mode cannot see what Mosaic refuses — unaligned blocks, layouts
it cannot build, more VMEM than a kernel may use — so these tests hand
the TPU compiler, which compiles for a chip that is described and not
attached, each kernel of the served path at real sizes.  Nothing runs:
a pass here is a compile, never a chip result.

The dispatcher sees the CPU, so every case calls a jitted kernel
function itself with ``interpret=False``.  The topology is described in
a module-scoped fixture (never at import: every test worker imports this
file, and only the one that runs it may load the TPU library).
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.codec import rice
from repro.core import schemes as S
from repro.kernels import backend as B
from repro.kernels import fused2d, fused3d, ops, tiled2d

MODE = "jpeg2000"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache out of it
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_mosaic(fn, *args, **static):
    """Compile ``fn`` for the described chip; its HLO must hold a kernel."""
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _bands_2d(bsz, h, w, sh):
    h_e, w_e, h_o, w_o = h - h // 2, w - w // 2, h // 2, w // 2
    return (
        _spec((bsz, h_e, w_e), sh), _spec((bsz, h_o, w_e), sh),
        _spec((bsz, h_e, w_o), sh), _spec((bsz, h_o, w_o), sh),
    )


def test_tiled_2d_fwd_inv_at_4096(one_chip):
    th, tw = B.pick_tile(4096, 4096, S.get_scheme("cdf53").halo)
    static = dict(mode=MODE, th=th, tw=tw, interpret=False)
    _assert_mosaic(tiled2d.fwd2d_tiled, _spec((1, 4096, 4096), one_chip), **static)
    _assert_mosaic(tiled2d.inv2d_tiled, *_bands_2d(1, 4096, 4096, one_chip), **static)


def test_whole_image_2d_fwd_inv_at_the_vmem_budget(one_chip):
    """The largest square the whole-image policy admits must compile —
    this is what keeps ``vmem_budget_bytes`` honest: a described v5e
    exposes no on-chip memory size, so the budget is the 16 MiB
    fallback, and the compiler has to accept the kernel it sizes."""
    assert B.vmem_budget_bytes() == 16 * 1024 * 1024
    side = math.isqrt(B.fused2d_budget_elems())
    assert fused2d.plan_2d(side, side, backend="pallas") != "xla"
    static = dict(scheme="cdf53", mode=MODE, interpret=False)
    _assert_mosaic(fused2d._fwd2d_pallas, _spec((1, side, side), one_chip), **static)
    _assert_mosaic(
        fused2d._inv2d_pallas, *_bands_2d(1, side, side, one_chip), **static
    )


def test_serve_pyramid_levels_5_at_4096(one_chip):
    """The serve executable's transform: five fused levels, tiled at the
    fine levels and whole-image at the coarse ones, one dispatch."""
    static = dict(
        levels=5, scheme="cdf53", mode=MODE, interpret=False,
        dispatch=B.dispatch_state(),
    )
    _assert_mosaic(
        fused2d._fwd2d_multi_kernel, _spec((4, 4096, 4096), one_chip), **static
    )


def test_1d_window_kernel_fwd_inv_at_5632(one_chip):
    rows, n = 2048, 5632
    assert B.pick_blocks(rows, n // 2) == (8, 256)
    static = dict(scheme="cdf53", mode=MODE, interpret=False)
    _assert_mosaic(ops._fwd_1d_kernel, _spec((rows, n), one_chip), **static)
    _assert_mosaic(
        ops._inv_1d_kernel,
        _spec((rows, n // 2), one_chip), _spec((rows, n // 2), one_chip),
        **static,
    )


def _bands_3d(bsz, d, h, w, sh):
    return tuple(
        _spec((bsz,) + dim, sh) for dim in fused3d._band_dims_3d(d, h, w)
    )


def test_whole_volume_3d_fwd_inv(one_chip):
    d, h, w = 16, 64, 64
    assert d * h * w <= B.fused3d_budget_elems()
    static = dict(scheme="cdf53", mode=MODE, interpret=False)
    _assert_mosaic(fused3d._fwd3d_pallas, _spec((1, d, h, w), one_chip), **static)
    _assert_mosaic(
        fused3d._inv3d_pallas, _bands_3d(1, d, h, w, one_chip), **static
    )


def test_slab_3d_fwd_inv(one_chip):
    d, h, w = 64, 128, 128
    halo = S.get_scheme("cdf53").halo
    td, th = B.pick_slab(d, h, w, halo)
    assert th is None  # whole planes: the plane fits the slab window
    static = dict(mode=MODE, td=td, interpret=False, scheme="cdf53")
    _assert_mosaic(fused3d.fwd3d_slab, _spec((1, d, h, w), one_chip), **static)
    _assert_mosaic(fused3d.inv3d_slab, _bands_3d(1, d, h, w, one_chip), **static)


# a CT series as the volume archive serves it: 256 slices of 512x512
CT_SERIES = (256, 512, 512)


def _mosaic_calls(text: str) -> int:
    return text.count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("shape", [CT_SERIES, (64, 512, 512)])
def test_plan_3d_keeps_512_planes_on_pallas(monkeypatch, shape):
    """At v5e's 16 MiB budget a 512x512 plane is over the smallest slab
    of whole planes, so the slab kernel tiles H: the plan a TPU resolves
    is ``slab-pallas``, not the XLA cliff."""
    monkeypatch.setattr(B, "platform", lambda: "tpu")
    assert B.vmem_budget_bytes() == 16 * 1024 * 1024
    halo = S.get_scheme("cdf53").halo
    assert B.pick_slab(*shape, halo)[1] is not None  # H is tiled
    assert fused3d.plan_3d(*shape) == "slab-pallas"
    assert fused3d.plan_3d(*shape, backend="pallas") == "slab-pallas"


def test_serve_volume_levels_5_at_ct_series(one_chip):
    """The volume serve executable's transform at (1, 256, 512, 512):
    five fused levels, every one a Mosaic kernel (plane-tiled slabs at
    the two finest levels), none left to XLA."""
    levels = 5
    static = dict(
        levels=levels, scheme="cdf53", mode=MODE, interpret=False,
        dispatch=B.dispatch_state(),
    )
    text = (
        fused3d._fwd3d_multi_kernel.lower(_spec((1,) + CT_SERIES, one_chip), **static)
        .compile()
        .as_text()
    )
    assert _mosaic_calls(text) == levels


def test_plane_tiled_slab_inverse_at_ct_series(one_chip):
    """One level of the inverse on the plane-tiled slab, at the series'
    finest level."""
    td, th = B.pick_slab(*CT_SERIES, S.get_scheme("cdf53").halo)
    assert th is not None
    static = dict(mode=MODE, td=td, th=th, interpret=False, scheme="cdf53")
    text = (
        fused3d.inv3d_slab.lower(_bands_3d(1, *CT_SERIES, one_chip), **static)
        .compile()
        .as_text()
    )
    assert _mosaic_calls(text) == 1


@pytest.mark.parametrize("nb", [1, 16, rice.CHUNK_BLOCKS])
def test_rice_encode_chunk(one_chip, nb):
    """The whole compiled chunk encode, at the smallest, a middle and the
    largest bucket: the pack is a Mosaic kernel and nothing scatters."""
    text = (
        rice._encode_chunk.lower(
            _spec((nb, rice.BLOCK_VALUES), one_chip), pack_backend="pallas"
        )
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in text
    assert "scatter" not in text


@pytest.mark.parametrize("nbytes", [8, 2048])
@pytest.mark.parametrize("nb", [1, 16, rice.CHUNK_BLOCKS])
def test_rice_decode_chunk(one_chip, nb, nbytes):
    """The whole compiled chunk decode, at the smallest, a middle and the
    largest row bucket, for the narrowest and the widest byte bucket: the
    unpack is a Mosaic kernel and nothing gathers."""
    text = (
        rice._decode_chunk.lower(
            _spec((nb, nbytes // 4), one_chip),
            _spec((nb,), one_chip),
            unpack_backend="pallas",
        )
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in text
    assert "gather" not in text
