"""shard_map row-sharded 2D DWT: halo-exchange correctness on a CPU mesh.

Runs in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count
(same pattern as test_distributed.py) so pytest's own process keeps its
single-device world.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.kernels.sharded import check_shardable

ROOT = Path(__file__).resolve().parents[1]


def _run(body: str, n_devices: int = 8) -> str:
    code = (
        "import os\n"
        f'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"\n'
        "import sys\n"
        f'sys.path.insert(0, {str(ROOT / "src")!r})\n' + textwrap.dedent(body)
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=540
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


@pytest.mark.slow
@pytest.mark.sharded
def test_sharded_fwd_inv_bit_exact_on_cpu_mesh():
    """4-way row sharding, both modes, multi-level, odd width, batch."""
    out = _run(
        """
        import numpy as np, jax.numpy as jnp
        from repro import kernels as K
        from repro.core import lifting
        from repro.kernels.sharded import check_shardable
        from repro.launch.mesh import make_mesh_compat

        mesh = make_mesh_compat((4, 2), ("data", "model"))
        rng = np.random.default_rng(11)
        checked = 0
        for mode in ("paper", "jpeg2000"):
            for lead in ((), (2,)):
                for (h, w) in ((64, 32), (64, 33), (96, 48), (64, 3)):
                    for levels in (1, 2, 3):
                        try:
                            check_shardable(h, w, 4, levels)
                        except ValueError:
                            continue
                        x = jnp.asarray(
                            rng.integers(-900, 900, lead + (h, w)), jnp.int32
                        )
                        want = lifting.dwt53_fwd_2d_multi(x, levels=levels, mode=mode)
                        got = K.dwt53_fwd_2d_sharded(x, mesh, levels=levels, mode=mode)
                        assert np.array_equal(np.asarray(got.ll), np.asarray(want.ll))
                        for gl, wl in zip(got.details, want.details):
                            for g, w_ in zip(gl, wl):
                                assert np.array_equal(np.asarray(g), np.asarray(w_))
                        xr = K.dwt53_inv_2d_sharded(got, mesh, mode=mode)
                        assert np.array_equal(np.asarray(xr), np.asarray(x))
                        checked += 1
        print("OK", checked)
        """
    )
    assert "OK" in out and int(out.split()[-1]) >= 20


@pytest.mark.slow
@pytest.mark.sharded
def test_sharded_output_stays_sharded():
    """Bands come back row-sharded (no silent all-gather of the result)."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro import kernels as K
        from repro.launch.mesh import make_mesh_compat

        mesh = make_mesh_compat((4,), ("data",))
        x = jnp.asarray(np.arange(64 * 16).reshape(64, 16), jnp.int32)
        pyr = K.dwt53_fwd_2d_sharded(x, mesh, levels=2)
        n_shards = len({d for d in pyr.ll.devices()})
        assert n_shards == 4, pyr.ll.sharding
        print("OK", n_shards)
        """
    )
    assert "OK 4" in out


@pytest.mark.slow
@pytest.mark.sharded
def test_sharded_per_scheme_bit_exact_on_cpu_mesh():
    """Scheme-derived halo exchange: haar ships no halo rows, 97m ships
    4 per direction — both bit-exact vs the single-device reference."""
    out = _run(
        """
        import numpy as np, jax.numpy as jnp
        from repro import kernels as K
        from repro.core import lifting
        from repro.kernels.sharded import check_shardable
        from repro.launch.mesh import make_mesh_compat

        mesh = make_mesh_compat((4,), ("data",))
        rng = np.random.default_rng(23)
        checked = 0
        for scheme in ("haar", "97m"):
            for (h, w, levels) in ((64, 32, 2), (64, 33, 1), (96, 24, 2)):
                check_shardable(h, w, 4, levels, scheme)
                x = jnp.asarray(rng.integers(-900, 900, (h, w)), jnp.int32)
                want = lifting.dwt_fwd_2d_multi(
                    x, levels=levels, scheme=scheme
                )
                got = K.dwt_fwd_2d_sharded(
                    x, mesh, levels=levels, scheme=scheme
                )
                assert np.array_equal(np.asarray(got.ll), np.asarray(want.ll))
                for gl, wl in zip(got.details, want.details):
                    for g, w_ in zip(gl, wl):
                        assert np.array_equal(np.asarray(g), np.asarray(w_))
                xr = K.dwt_inv_2d_sharded(got, mesh, scheme=scheme)
                assert np.array_equal(np.asarray(xr), np.asarray(x))
                checked += 1
        print("OK", checked)
        """,
        n_devices=4,
    )
    assert "OK" in out and int(out.split()[-1]) >= 6


def test_check_shardable_rejects_bad_shapes():
    with pytest.raises(ValueError, match="divisible"):
        check_shardable(60, 32, 4, 2)  # 60 % (4*4) != 0
    with pytest.raises(ValueError, match="W >= 3"):
        check_shardable(64, 2, 4, 1)
    with pytest.raises(ValueError, match="W >= 3"):
        check_shardable(128, 5, 4, 3)  # width hits 2 at level 3
    with pytest.raises(ValueError, match="levels"):
        check_shardable(64, 32, 4, 0)
    check_shardable(64, 32, 4, 2)  # and a valid one passes


@pytest.mark.slow
@pytest.mark.sharded
def test_spatial_2d_pod_sync_converges_to_mean():
    """The spatial_2d gradient codec inside shard_map: per-band ring sums
    + pmax'd shifts reconstruct ~the cross-pod mean for matrix leaves."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.train.grad_compress import WaveletSyncConfig, pod_sync_tree
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((2,), ("pod",))
        rng = np.random.default_rng(5)
        grads = {"w": jnp.asarray(rng.normal(size=(2, 64, 96)), jnp.float32),
                 "skinny": jnp.asarray(rng.normal(size=(2, 2, 4096)), jnp.float32),
                 "v": jnp.asarray(rng.normal(size=(2, 8000)), jnp.float32)}
        err = {"w": jnp.zeros((64, 96), jnp.float32),
               "skinny": jnp.zeros((2, 4096), jnp.float32),
               "v": jnp.zeros((8000,), jnp.float32)}
        cfg = WaveletSyncConfig(levels=2, codec="bands", n_pods=2,
                                min_size=256, spatial_2d=True)
        f = jax.shard_map(lambda g, e: pod_sync_tree(g, e, cfg, axis_name="pod"),
                          mesh=mesh, in_specs=(P("pod"), P()),
                          out_specs=(P(), P()), check_vma=False)
        synced, new_err = jax.jit(f)(grads, err)
        for k, g in grads.items():
            want = np.mean(np.asarray(g), axis=0)
            got = np.asarray(synced[k])
            rel = np.linalg.norm(got - want) / np.linalg.norm(want)
            assert rel < 0.05, (k, rel)
            assert np.isfinite(np.asarray(new_err[k])).all(), k
        print("OK")
        """,
        n_devices=2,
    )
    assert "OK" in out


@pytest.mark.slow
@pytest.mark.sharded
def test_spatial_3d_pod_sync_converges_to_mean():
    """The spatial_3d gradient codec inside shard_map: volume-shaped
    leaves route through the fused 3D pyramid (kernels/fused3d.py),
    per-band ring sums + pmax'd shifts reconstruct ~the cross-pod mean,
    and matrix/vector leaves still fall through to the 2D/1D codecs."""
    out = _run(
        """
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.train.grad_compress import WaveletSyncConfig, pod_sync_tree
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((2,), ("pod",))
        rng = np.random.default_rng(7)
        grads = {"act": jnp.asarray(rng.normal(size=(2, 6, 16, 24)), jnp.float32),
                 "w": jnp.asarray(rng.normal(size=(2, 64, 96)), jnp.float32),
                 "v": jnp.asarray(rng.normal(size=(2, 8000)), jnp.float32)}
        err = {"act": jnp.zeros((6, 16, 24), jnp.float32),
               "w": jnp.zeros((64, 96), jnp.float32),
               "v": jnp.zeros((8000,), jnp.float32)}
        cfg = WaveletSyncConfig(levels=2, codec="bands", n_pods=2,
                                min_size=256, spatial_3d=True, spatial_2d=True)
        f = jax.shard_map(lambda g, e: pod_sync_tree(g, e, cfg, axis_name="pod"),
                          mesh=mesh, in_specs=(P("pod"), P()),
                          out_specs=(P(), P()), check_vma=False)
        synced, new_err = jax.jit(f)(grads, err)
        for k, g in grads.items():
            want = np.mean(np.asarray(g), axis=0)
            got = np.asarray(synced[k])
            rel = np.linalg.norm(got - want) / np.linalg.norm(want)
            assert rel < 0.05, (k, rel)
            assert np.isfinite(np.asarray(new_err[k])).all(), k
        print("OK")
        """,
        n_devices=2,
    )
    assert "OK" in out
