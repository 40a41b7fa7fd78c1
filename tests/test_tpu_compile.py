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
    assert B.slab_fits(h, w, halo)
    td = B.pick_slab(d, h, w, halo)
    static = dict(mode=MODE, td=td, interpret=False, scheme="cdf53")
    _assert_mosaic(fused3d.fwd3d_slab, _spec((1, d, h, w), one_chip), **static)
    _assert_mosaic(fused3d.inv3d_slab, _bands_3d(1, d, h, w, one_chip), **static)


def test_rice_pack_kernel(one_chip):
    pack = jax.jit(lambda b: rice._pack_words_pallas(b, interpret=False))
    _assert_mosaic(pack, _spec((rice.CHUNK_BLOCKS, 32, rice._WORDS), one_chip))
