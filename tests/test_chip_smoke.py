"""chip_smoke.py off the chip: its phases at tiny sizes, its refusal.

The phases run here on the CPU with the Pallas kernels under the
interpreter, which covers their control flow and bit-exact checks; the
real sizes run only on a TPU (``python chip_smoke.py``).
"""
import json
import sys
from pathlib import Path

import jax
import pytest

from repro.kernels import backend as B
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402


def test_serve_phase_tiny_interpret():
    with B.use_backend("interpret"):
        res = chip_smoke.serve_phase(
            buckets=((32, 32), (64, 64)), batch_slots=2, levels=2,
            shapes=((64, 64), (40, 60), (32, 32), (20, 30)), seed=3,
            expect="interpret",
        )
    assert res["ok"], res
    assert res["requests"] == 4 and res["misses_after_warmup"] == 0
    assert res["pyramid_exact"] and res["roundtrip_exact"] and res["lowband_exact"]
    assert set(res["dispatch"]) == {"interpret"}


def test_ckpt_phase_tiny_interpret(tmp_path):
    with B.use_backend("interpret"):
        res = chip_smoke.ckpt_phase(32, 88, tmp_path, seed=3, expect="interpret")
    assert res["ok"], res
    assert res["routes"] == ["1d", "2d"]
    assert res["params"] == 4 * 32 * 32 + 3 * 32 * 88 + 4 * 32


def test_mesh_phase_tiny_one_device():
    res = chip_smoke.mesh_phase(1, (64, 64), n_images=2, levels=2, seed=3)
    assert res["ok"], res


def test_main_refuses_a_platform_without_tpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("runs only off the chip")
    assert chip_smoke.main([]) != 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1])["phase"] == "device"
    assert not any('"ok": true' in line for line in lines)


def test_compile_cache_follows_env_or_checkout(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.cache_dir() == str(ROOT / ".jax_cache")
