#!/usr/bin/env python3
"""Chip smoke test: the lossless tile-pyramid path on a TPU, end to end.

    python chip_smoke.py             # one chip: the serve and ckpt phases
    python chip_smoke.py --chips 4   # four chips: the row-sharded mesh route

Each phase drives the system through the entry points its users call and
checks every output bit-exactly against the plain oracle
(``core.lifting``):

  serve  ``WaveletServeEngine`` with JPEG 2000 Part 1 buckets (2048² and
         4096², five levels, reversible 5/3) and batch WZRC responses:
         pyramid == oracle, ``decode_batch`` + inverse == the image,
         ``decode_lowband`` == the oracle LL, no compile after warm-up.
  ckpt   ``CheckpointManager(codec="wz-rice")`` saving and restoring one
         stablelm-1.6b transformer layer at its published widths.
  mesh   (``--chips 4`` only) the engine on a 4-device row mesh at 8192²,
         against the one-chip Pallas pyramid and the oracle, with a
         sharded inverse round trip and the shards on 4 devices.

Every phase prints one JSON line of facts, and fails unless the kernels
dispatched only to compiled Pallas, nothing degraded and no fault was
recorded.  The last line, ``{"ok": true, "device": {...}}``, is printed
only when every phase passed on a TPU; otherwise the exit code is 1.
One process, no children: the process that imports JAX holds the chip.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# JPEG 2000 Part 1 lossless tile pyramid: reversible 5/3, 5 levels (the
# Part 1 default decomposition depth), 16-bit samples DC-shifted to signed
SERVE_BUCKETS = ((2048, 2048), (4096, 4096))
SERVE_SLOTS = 4
SERVE_LEVELS = 5
SERVE_SHAPES = (
    (4096, 4096), (3000, 4000), (4096, 4096), (2500, 4096),
    (2048, 2048), (1500, 2000), (2048, 2048), (1024, 2048),
)
# one transformer layer of stablelm-1.6b (configs/stablelm_1_6b.py)
CKPT_D_MODEL = 2048
CKPT_D_FF = 5632
MESH_BUCKET = (8192, 8192)
MESH_IMAGES = 2
SEED = 1010
MODE = "jpeg2000"
SCHEME = "cdf53"


def _kernel_facts() -> dict:
    """Dispatch counts by resolved backend, degrades and fault events
    recorded by ``repro.obs`` since the last reset."""
    from repro import obs

    dispatch: dict = {}
    degrades = 0
    for key, value in obs.registry.snapshot().items():
        if key.startswith("kernels.dispatch{"):
            resolved = re.search(r'resolved="([^"]+)"', key).group(1)
            dispatch[resolved] = dispatch.get(resolved, 0) + int(value)
        elif key.startswith("kernels.degrades"):
            degrades += int(value)
    faults = int(obs.events.counts().get("FaultEvent", 0))
    return {"dispatch": dispatch, "degrades": degrades, "fault_events": faults}


def _clean_kernels(facts: dict, expect: str) -> bool:
    """Only ``expect`` dispatches, at least one, no degrade, no fault."""
    d = facts["dispatch"]
    return (
        d.get(expect, 0) > 0 and set(d) == {expect}
        and facts["degrades"] == 0 and facts["fault_events"] == 0
    )


def _equal(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _oracle_2d(levels: int):
    import jax

    from repro.core import lifting

    return jax.jit(
        lambda x: lifting.dwt_fwd_2d_multi(x, levels=levels, mode=MODE, scheme=SCHEME)
    )


def _images(shapes, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(-32768, 32768, s, dtype=np.int32) for s in shapes]


def serve_phase(buckets, batch_slots: int, levels: int, shapes, seed: int,
                expect: str = "pallas") -> dict:
    """Bucketed serving with batch WZRC responses, checked bit-exactly."""
    from repro import codec, obs
    from repro import kernels as K
    from repro.kernels import backend as B
    from repro.kernels import fused2d
    from repro.serve import ExecKey, TransformRequest, WaveletServeEngine

    obs.reset()
    eng = WaveletServeEngine(
        buckets=buckets, batch_slots=batch_slots, levels=levels,
        scheme=SCHEME, mode=MODE, encode_response=True,
    )
    t0 = time.perf_counter()
    eng.warmup()
    # one request per bucket compiles the Rice coder too: set-up time
    eng.run([
        TransformRequest(uid=-1 - i, image=img)
        for i, img in enumerate(_images(buckets, seed + 1))
    ])
    setup_s = time.perf_counter() - t0
    misses0 = eng.executor.misses

    images = _images(shapes, seed)
    t0 = time.perf_counter()
    done = eng.run([TransformRequest(uid=i, image=img) for i, img in enumerate(images)])
    steady_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    oracle = _oracle_2d(levels)
    decoded: dict = {}
    pyramid_exact = roundtrip_exact = lowband_exact = True
    for r in done:
        img = images[r.uid]
        padded = np.zeros(r.bucket, np.int32)
        padded[: img.shape[0], : img.shape[1]] = img
        want = oracle(padded)
        pyramid_exact &= r.error is None and _equal(r.pyramid, want)
        if id(r.encoded) not in decoded:
            decoded[id(r.encoded)] = codec.decode_batch(r.encoded)
        item = decoded[id(r.encoded)][r.batch_index]
        back = np.asarray(K.dwt_inv_2d_multi(item, mode=MODE, scheme=SCHEME))
        roundtrip_exact &= np.array_equal(back[: img.shape[0], : img.shape[1]], img)
        thumb = codec.decode_lowband(r.encoded).band[r.batch_index]
        lowband_exact &= _equal(thumb, want.ll)
    verify_s = time.perf_counter() - t0

    key = ExecKey(tuple(buckets[-1]), batch_slots, SCHEME, levels, MODE, None, None)
    hlo_kernel = "tpu_custom_call" in eng.executor.executable(key).as_text()
    facts = _kernel_facts()
    result = {
        "phase": "serve",
        "plan_2d": {
            "x".join(map(str, b)): fused2d.plan_2d(*b, scheme=SCHEME) for b in buckets
        },
        "vmem_budget_bytes": B.vmem_budget_bytes(),
        "requests": len(done),
        "setup_s": setup_s,
        "steady_s": steady_s,
        "verify_s": verify_s,
        "misses_after_warmup": eng.executor.misses - misses0,
        "hlo_tpu_custom_call": hlo_kernel,
        "pyramid_exact": bool(pyramid_exact),
        "roundtrip_exact": bool(roundtrip_exact),
        "lowband_exact": bool(lowband_exact),
        **facts,
    }
    result["ok"] = (
        len(done) == len(shapes)
        and pyramid_exact and roundtrip_exact and lowband_exact
        and result["misses_after_warmup"] == 0
        and (hlo_kernel or expect != "pallas")
        and _clean_kernels(facts, expect)
    )
    return result


def transformer_layer(d_model: int, d_ff: int, seed: int) -> dict:
    """One dense transformer layer's parameters (random, seeded): four
    attention projections, a SwiGLU MLP and two LayerNorms."""
    rng = np.random.default_rng(seed)

    def w(*shape):
        return (rng.standard_normal(shape, np.float32) * 0.02).astype(np.float32)

    return {
        "attn": {name: w(d_model, d_model) for name in ("wq", "wk", "wv", "wo")},
        "mlp": {
            "w_gate": w(d_model, d_ff), "w_up": w(d_model, d_ff),
            "w_down": w(d_ff, d_model),
        },
        "ln1": {"scale": 1 + w(d_model), "bias": w(d_model)},
        "ln2": {"scale": 1 + w(d_model), "bias": w(d_model)},
    }


def ckpt_phase(d_model: int, d_ff: int, directory, seed: int,
               expect: str = "pallas") -> dict:
    """A ``wz-rice`` checkpoint save + restore of one transformer layer."""
    import jax

    from repro import obs
    from repro.ckpt.checkpoint import CheckpointManager

    obs.reset()
    tree = transformer_layer(d_model, d_ff, seed)
    mgr = CheckpointManager(directory, codec="wz-rice")
    t0 = time.perf_counter()
    mgr.save(1, tree)
    save_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    step, out = mgr.restore(template=tree)
    restore_s = time.perf_counter() - t0

    # the tests/test_codec.py wz-rice check: full int16 quantization, so
    # the restore error is at most half a quantization step per leaf
    within = jax.tree_util.tree_map(
        lambda a, b: bool(
            np.max(np.abs(b - a)) <= np.max(np.abs(a)) / 32767 * 0.51
        ),
        tree, out,
    )
    manifest = json.loads(
        (Path(directory) / "step_0000000001" / "manifest.json").read_text()
    )
    metas = [m["meta"] for m in manifest["leaves"].values()]
    routes = sorted({m["enc"] for m in metas})
    facts = _kernel_facts()
    result = {
        "phase": "ckpt",
        "params": int(sum(a.size for a in jax.tree_util.tree_leaves(tree))),
        "save_s": save_s,
        "restore_s": restore_s,
        "routes": routes,
        "restore_within_half_step": all(jax.tree_util.tree_leaves(within)),
        **facts,
    }
    result["ok"] = (
        step == 1
        and result["restore_within_half_step"]
        and routes == ["1d", "2d"]
        and all(m["enc_version"] == 2 and m["parity"] for m in metas)
        and _clean_kernels(facts, expect)
    )
    return result


def mesh_phase(n_devices: int, bucket, n_images: int, levels: int, seed: int) -> dict:
    """The row-sharded mesh route against one chip and the oracle."""
    import jax
    from jax.sharding import Mesh

    from repro import kernels as K
    from repro import obs
    from repro.serve import TransformRequest, WaveletServeEngine

    obs.reset()
    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("data",))
    eng = WaveletServeEngine(
        buckets=(bucket,), batch_slots=1, levels=levels, scheme=SCHEME,
        mode=MODE, mesh=mesh,
    )
    t0 = time.perf_counter()
    eng.warmup()
    setup_s = time.perf_counter() - t0
    images = _images((bucket,) * n_images, seed)
    t0 = time.perf_counter()
    done = eng.run([TransformRequest(uid=i, image=img) for i, img in enumerate(images)])
    jax.block_until_ready([r.pyramid for r in done])
    steady_s = time.perf_counter() - t0

    oracle = _oracle_2d(levels)
    vs_one_chip = vs_oracle = roundtrip = spread = True
    for r in done:
        img = images[r.uid]
        one_chip = K.dwt_fwd_2d_multi(img, levels=levels, mode=MODE, scheme=SCHEME)
        vs_one_chip &= r.error is None and _equal(r.pyramid, one_chip)
        vs_oracle &= _equal(r.pyramid, oracle(img))
        back = K.dwt_inv_2d_sharded(r.pyramid, mesh, mode=MODE, scheme=SCHEME)
        roundtrip &= np.array_equal(np.asarray(back), img)
        for band in jax.tree_util.tree_leaves(r.pyramid):
            devices = {s.device for s in band.addressable_shards}
            spread &= len(devices) == n_devices
    result = {
        "phase": "mesh",
        "devices": n_devices,
        "bucket": "x".join(map(str, bucket)),
        "requests": len(done),
        "setup_s": setup_s,
        "steady_s": steady_s,
        "vs_one_chip_exact": bool(vs_one_chip),
        "vs_oracle_exact": bool(vs_oracle),
        "sharded_roundtrip_exact": bool(roundtrip),
        "shards_on_distinct_devices": bool(spread),
    }
    result["ok"] = len(done) == n_images and vs_one_chip and vs_oracle and roundtrip and spread
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: serve + ckpt phases on one chip; 4: the mesh phase only",
    )
    args = ap.parse_args(argv)

    import jax

    from repro.launch import compile_cache

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"phase": "device", **device}), flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {device['count']} found",
              file=sys.stderr)
        return 1
    print(json.dumps({"phase": "cache", "dir": compile_cache.enable()}), flush=True)

    ok = True
    if args.chips == 4:
        phases = [lambda: mesh_phase(4, MESH_BUCKET, MESH_IMAGES, SERVE_LEVELS, SEED)]
    else:
        def ckpt():
            with tempfile.TemporaryDirectory() as d:
                return ckpt_phase(CKPT_D_MODEL, CKPT_D_FF, d, SEED)

        phases = [
            lambda: serve_phase(
                SERVE_BUCKETS, SERVE_SLOTS, SERVE_LEVELS, SERVE_SHAPES, SEED
            ),
            ckpt,
        ]
    for phase in phases:
        result = phase()
        print(json.dumps(result), flush=True)
        ok &= bool(result["ok"])
    if not ok:
        print("chip_smoke: a phase failed (see its line above)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
