"""Distribution tests that need multiple devices: run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (pytest's own process
keeps 1 device so every other test sees the normal CPU world)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(body: str) -> str:
    code = (
        "import os\n"
        'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"\n'
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
def test_wavelet_multipod_step_matches_baseline():
    out = _run(
        """
        import jax, jax.numpy as jnp
        from repro.configs import get_config, reduced
        from repro.launch.train import init_train_state
        from repro.train.train_step import (make_wavelet_train_step, make_train_step,
            init_podded_error_feedback, podded, podded_opt)
        from repro.train.grad_compress import WaveletSyncConfig
        from repro.train import optim
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = reduced(get_config("stablelm-1.6b"))
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((2,2,2), ("pod","data","model"))
        state = init_train_state(cfg, 0)
        opt_cfg = optim.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        sync = WaveletSyncConfig(levels=2, codec="bands", n_pods=2, min_size=256)
        wstep = make_wavelet_train_step(cfg, mesh, opt_cfg, sync)
        bstep = jax.jit(make_train_step(cfg, opt_cfg))
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8))
        with mesh:
            pw = podded(state["params"], 2); ow = podded_opt(state["opt"], 2)
            err = init_podded_error_feedback(state["params"], 2)
            pb, ob = state["params"], state["opt"]
            for s in range(6):
                b = {k: jnp.asarray(v) for k, v in data.batch(s).items()}
                pw, ow, err, mw = wstep(pw, ow, err, b)
                pb, ob, mb = bstep(pb, ob, b)
            leaf = jax.tree_util.tree_leaves(pw)[3]
            assert bool(jnp.array_equal(leaf[0], leaf[1])), "pod replicas diverged"
            dw, db = float(mw["loss"]), float(mb["loss"])
            assert abs(dw - db) / db < 0.05, (dw, db)
            print("OK", dw, db)
        """
    )
    assert "OK" in out


@pytest.mark.slow
@pytest.mark.sharded
def test_pjit_train_step_sharded_mesh():
    """The plain train step on a (data=2, model=2) mesh with real arrays."""
    out = _run(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import sharding as SH
        from repro.configs import get_config, reduced
        from repro.launch.train import init_train_state
        from repro.models import layers as L, transformer as T
        from repro.train import optim
        from repro.train.train_step import make_train_step

        cfg = reduced(get_config("granite-3-8b"))
        from repro.launch.mesh import make_mesh_compat
        mesh = make_mesh_compat((2,2), ("data","model"))
        rules = SH.rules_for(mesh, multi_pod=False, fsdp=False, n_heads=cfg.n_heads,
                             n_kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
                             d_model=cfg.d_model, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                             global_batch=4)
        state = init_train_state(cfg, 0)
        axes = L.logical_axes(T.model_defs(cfg))
        shardings = jax.tree_util.tree_map(
            lambda a: NamedSharding(mesh, SH.spec_for(a, rules)), axes,
            is_leaf=lambda v: isinstance(v, tuple) and all(
                x is None or isinstance(x, str) for x in v))
        params = jax.device_put(state["params"], shardings)
        opt = optim.adamw_init(params)
        step = jax.jit(make_train_step(cfg, optim.AdamWConfig(lr=1e-3)))
        batch = {"tokens": jnp.ones((4, 64), jnp.int32),
                 "labels": jnp.ones((4, 64), jnp.int32)}
        with mesh, SH.logical_rules(rules, mesh):
            p, o, m = step(params, opt, batch)
        assert bool(jnp.isfinite(m["loss"]))
        print("OK", float(m["loss"]))
        """
    )
    assert "OK" in out


@pytest.mark.slow
@pytest.mark.sharded
def test_dryrun_cell_on_debug_mesh():
    """One dry-run cell end-to-end in a subprocess (its own 512-dev world)."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "musicgen-medium",
         "--cell", "decode_32k", "--debug-mesh", "2,2,2", "--no-save"],
        capture_output=True, text=True, timeout=540,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src")}, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr[-2000:]
    assert "OK musicgen-medium" in proc.stdout


@pytest.mark.slow
@pytest.mark.sharded
def test_microbatch_accumulation_equivalence():
    out = _run(
        """
        import jax, jax.numpy as jnp
        from repro.configs import get_config, reduced
        from repro.launch.train import init_train_state
        from repro.train import optim
        from repro.train.train_step import make_train_step

        cfg = reduced(get_config("stablelm-1.6b"))
        state = init_train_state(cfg, 0)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size)}
        oc = optim.AdamWConfig(lr=1e-3)
        s1 = jax.jit(make_train_step(cfg, oc, n_microbatches=1))
        s2 = jax.jit(make_train_step(cfg, oc, n_microbatches=2))
        p1, o1, m1 = s1(state["params"], state["opt"], batch)
        p2, o2, m2 = s2(state["params"], state["opt"], batch)
        l1 = jax.tree_util.tree_leaves(p1)[0]
        l2 = jax.tree_util.tree_leaves(p2)[0]
        import numpy as np
        # microbatch mean-of-means == full-batch mean here (equal splits)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-3, atol=2e-5)
        print("OK")
        """
    )
    assert "OK" in out
