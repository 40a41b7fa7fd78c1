import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the process entry point (the XLA_FLAGS line above runs before any
jax import, because jax locks the device count on first init).

For each cell:
  * abstract params / optimizer state / caches (ShapeDtypeStruct, no alloc)
  * jit with NamedShardings from the logical rules
  * .lower() -> .compile()
  * record memory_analysis(), cost_analysis(), and the collective schedule
    parsed from the partitioned HLO  ->  artifacts/dryrun/<cell>.json

Usage:
  python -m repro.launch.dryrun --arch granite-34b --cell train_4k [--multipod]
  python -m repro.launch.dryrun --all [--multipod]
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import roofline as RL
from repro import sharding as SH
from repro.configs import ARCH_IDS, get_config, shape_cell, cell_applicable
from repro.configs.base import ArchConfig, ShapeCell
from repro.launch.mesh import make_production_mesh
from repro.models import layers as L
from repro.models import transformer as T
from repro.train import optim
from repro.train.train_step import make_train_step

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def hillclimb_overrides(cfg: ArchConfig) -> ArchConfig:
    """Env-gated beyond-baseline knobs so §Perf before/after runs are both
    reproducible from the same code:

      REPRO_OPT_CE_CHUNK=<n>    chunked fp32 cross-entropy (memory/bytes)
      REPRO_OPT_REMAT_DOTS=1    save matmul outputs in remat (compute)
      REPRO_OPT_ATTN_CHUNK=<n>  attention chunk size
    """
    import dataclasses as _dc

    kw = {}
    if os.environ.get("REPRO_OPT_CE_CHUNK"):
        kw["ce_chunk"] = int(os.environ["REPRO_OPT_CE_CHUNK"])
    if os.environ.get("REPRO_OPT_REMAT_DOTS"):
        kw["remat_policy"] = "dots"
    if os.environ.get("REPRO_OPT_ATTN_CHUNK"):
        kw["attn_chunk"] = int(os.environ["REPRO_OPT_ATTN_CHUNK"])
    if os.environ.get("REPRO_OPT_MOE_INT16") and cfg.moe is not None:
        kw["moe"] = _dc.replace(cfg.moe, dispatch_dtype="int16")
    if os.environ.get("REPRO_OPT_MOE_CF"):
        kw["moe"] = _dc.replace(
            kw.get("moe", cfg.moe), capacity_factor=float(os.environ["REPRO_OPT_MOE_CF"])
        )
    return _dc.replace(cfg, **kw) if kw else cfg


def rules_for_cell(cfg: ArchConfig, cell: ShapeCell, mesh, multi_pod: bool):
    # REPRO_OPT_KV_REPLICATE=1: replicate non-model-divisible KV heads for
    # train/prefill instead of sharding the QK^T contraction dim (§Perf);
    # REPRO_OPT_ATTN_REPLICATE=1 extends this to the Q heads axis too
    prefer_rep = bool(os.environ.get("REPRO_OPT_KV_REPLICATE")) and cell.kind != "decode"
    prefer_rep_attn = bool(os.environ.get("REPRO_OPT_ATTN_REPLICATE"))
    return SH.rules_for(
        mesh,
        multi_pod=multi_pod,
        fsdp=cfg.fsdp and cell.kind == "train",
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim,
        d_model=cfg.d_model,
        d_ff=cfg.d_ff,
        vocab=cfg.vocab_size,
        global_batch=cell.global_batch,
        prefer_replicated_kv=prefer_rep,
        prefer_replicated_attn=prefer_rep_attn,
    )


def input_specs(cfg: ArchConfig, cell: ShapeCell) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = cell.global_batch, cell.seq_len
    cdt = _dtype(cfg.compute_dtype)
    if cell.kind == "train":
        if cfg.input_mode == "tokens":
            return {
                "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
            }
        return {
            "embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), cdt),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
    if cell.kind == "prefill":
        if cfg.input_mode == "tokens":
            return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        return {"embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), cdt)}
    # decode: one new token, cache of length s
    if cfg.input_mode == "tokens":
        return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    return {"embeds": jax.ShapeDtypeStruct((b, 1, cfg.d_model), cdt)}


def batch_specs_sharding(specs, rules, mesh):
    def spec_of(sds: jax.ShapeDtypeStruct):
        if sds.ndim >= 2 and sds.shape[-1] > 4096 or sds.ndim == 3:
            axes = ("batch", "seq", "embed")[: sds.ndim]
        else:
            axes = ("batch", "seq")[: sds.ndim]
        return NamedSharding(mesh, SH.spec_for(axes[: sds.ndim], rules))

    return {k: spec_of(v) for k, v in specs.items()}


def _abstract(tree, dtype):
    return L.abstract_params(tree, dtype)


def build_cell(
    cfg: ArchConfig, cell: ShapeCell, mesh, multi_pod: bool
) -> Tuple[Any, Tuple, Any]:
    """Returns (jitted_fn, abstract_args, rules)."""
    rules = rules_for_cell(cfg, cell, mesh, multi_pod)
    pdt = _dtype(cfg.param_dtype)
    defs = T.model_defs(cfg)
    params_abs = L.abstract_params(defs, pdt)
    axes = L.logical_axes(defs)
    param_shardings = jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, SH.spec_for(a, rules)),
        axes,
        is_leaf=lambda v: isinstance(v, tuple)
        and all(x is None or isinstance(x, str) for x in v),
    )
    ins = input_specs(cfg, cell)
    in_shard = batch_specs_sharding(ins, rules, mesh)

    if cell.kind == "train":
        opt_abs = optim.AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            m=jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_abs
            ),
            v=jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_abs
            ),
        )
        opt_shardings = optim.AdamWState(
            step=NamedSharding(mesh, P()),
            m=param_shardings,
            v=param_shardings,
        )
        step = make_train_step(cfg, ce_chunk=cfg.ce_chunk)

        def fn(params, opt_state, batch):
            with SH.logical_rules(rules, mesh):
                return step(params, opt_state, batch)

        jitted = jax.jit(
            fn,
            in_shardings=(param_shardings, opt_shardings, in_shard),
            out_shardings=(param_shardings, opt_shardings, None),
        )
        args = (params_abs, opt_abs, ins)
        return jitted, args, rules

    if cell.kind == "prefill":

        def fn(params, batch):
            with SH.logical_rules(rules, mesh):
                return T.prefill(params, cfg, **batch)

        jitted = jax.jit(fn, in_shardings=(param_shardings, in_shard))
        return jitted, (params_abs, ins), rules

    # decode
    cache_abs = jax.eval_shape(
        lambda: T.init_caches(cfg, cell.global_batch, cell.seq_len)
    )
    cache_shardings = jax.tree_util.tree_map(
        lambda a: NamedSharding(mesh, SH.spec_for(a, rules)),
        T.cache_axes(cfg),
        is_leaf=lambda v: isinstance(v, tuple)
        and all(x is None or isinstance(x, str) for x in v),
    )

    def fn(params, caches, batch):
        with SH.logical_rules(rules, mesh):
            return T.decode_step(params, cfg, caches, **batch)

    jitted = jax.jit(
        fn,
        in_shardings=(param_shardings, cache_shardings, in_shard),
        out_shardings=(None, cache_shardings),
    )
    return jitted, (params_abs, cache_abs, ins), rules


import dataclasses


def _probe_layer_counts(cfg: ArchConfig) -> Tuple[int, ...]:
    """Layer counts for the unrolled cost probes (see _probe_costs)."""
    if cfg.family == "hybrid":
        return (3, 6, 5)  # 1 super | 2 supers | 1 super + 2 tail rec
    return (1, 2)


def probe_cfg(cfg: ArchConfig, cell: ShapeCell, n_layers: int) -> ArchConfig:
    """Cost-probe variant: unrolled loops so cost_analysis counts true trip
    counts (XLA counts while bodies ONCE — verified, see DESIGN.md §7)."""
    return dataclasses.replace(
        cfg,
        n_layers=n_layers,
        scan_layers=False,
        unroll_loops=True,
        attn_chunk=max(cell.seq_len // 4, 128) if cell.kind != "decode" else cfg.attn_chunk,
        rwkv_chunk=max(min(cell.seq_len // 4, 8192), 16),
    )


def _cost_of(cfg: ArchConfig, cell: ShapeCell, mesh, multi_pod: bool, chips: int):
    jitted, args, _ = build_cell(cfg, cell, mesh, multi_pod)
    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = RL.parse_collectives(compiled.as_text(), chips)
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        float(coll.wire_bytes_per_device),
        dict(coll.counts),
        dict(coll.by_op_bytes),
    )


def _probe_costs(
    cfg: ArchConfig, cell: ShapeCell, mesh, multi_pod: bool, chips: int
) -> Dict[str, Any]:
    """Trip-count-correct (flops, bytes, wire/device) by linear extrapolation
    over unrolled 1-layer / 2-layer probes (hybrid: 1/2 super + tail)."""
    counts = _probe_layer_counts(cfg)
    probes = {}
    for lc in counts:
        probes[lc] = _cost_of(probe_cfg(cfg, cell, lc), cell, mesh, multi_pod, chips)

    def extrap(idx: int) -> float:
        if cfg.family == "hybrid":
            c3, c6, c5 = probes[3][idx], probes[6][idx], probes[5][idx]
            n_super, n_tail = hybrid_layout_counts(cfg)
            per_super = c6 - c3
            tail = (c5 - c3) * (n_tail / 2.0)
            return c3 + (n_super - 1) * per_super + tail
        c1, c2 = probes[counts[0]][idx], probes[counts[1]][idx]
        per_layer = c2 - c1
        return c1 + (cfg.n_layers - 1) * per_layer

    # extrapolate per-op wire bytes the same way (for bottleneck diagnosis)
    by_op = {}
    keys = set()
    for v in probes.values():
        keys |= set(v[4])
    if cfg.family != "hybrid":
        c1, c2 = probes[counts[0]][4], probes[counts[1]][4]
        for k in keys:
            a, b = c1.get(k, 0.0), c2.get(k, 0.0)
            by_op[k] = a + (cfg.n_layers - 1) * (b - a)
    else:
        by_op = dict(probes[counts[1]][4])
    return {
        "flops": extrap(0),
        "bytes": extrap(1),
        "wire_per_device": extrap(2),
        "by_op_bytes": by_op,
        "probe_points": {str(k): v[:3] for k, v in probes.items()},
        "collective_counts_probe": probes[counts[-1]][3],
    }


def hybrid_layout_counts(cfg: ArchConfig) -> Tuple[int, int]:
    p = cfg.hybrid.attn_period
    return cfg.n_layers // p, cfg.n_layers % p


def run_cell(
    arch: str,
    cell_name: str,
    multi_pod: bool,
    save: bool = True,
    debug_mesh: Optional[Tuple[int, ...]] = None,
    probe: bool = True,
) -> Dict[str, Any]:
    cfg = hillclimb_overrides(get_config(arch))
    cell = shape_cell(cell_name)
    ok, why = cell_applicable(cfg, cell)
    if debug_mesh is not None:
        mesh_name = "debug" + "x".join(map(str, debug_mesh))
    else:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result: Dict[str, Any] = {
        "arch": arch,
        "cell": cell_name,
        "mesh": mesh_name,
        "status": "SKIP",
        "reason": why,
    }
    if not ok:
        print(f"[dryrun] {arch} x {cell_name} x {mesh_name}: {why}")
        if save:
            _save(result)
        return result

    if debug_mesh is not None:
        axes = ("pod", "data", "model") if len(debug_mesh) == 3 else ("data", "model")
        multi_pod = len(debug_mesh) == 3
        from repro.launch.mesh import make_mesh_compat

        mesh = make_mesh_compat(debug_mesh, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    try:
        jitted, args, rules = build_cell(cfg, cell, mesh, multi_pod)
        with mesh:
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = RL.parse_collectives(hlo, chips)
        model_flops = RL.model_flops_for(
            cfg, cell, cfg.param_count(), cfg.active_param_count()
        )
        # trip-count-correct costs via unrolled probes (scan bodies are
        # counted once by XLA cost analysis; see DESIGN.md §7)
        probe_data = None
        if probe:
            try:
                probe_data = _probe_costs(cfg, cell, mesh, multi_pod, chips)
                cost = {
                    "flops": probe_data["flops"],
                    "bytes accessed": probe_data["bytes"],
                }
                coll = RL.CollectiveStats(
                    counts=probe_data["collective_counts_probe"],
                    wire_bytes_per_device=probe_data["wire_per_device"],
                    by_op_bytes=probe_data.get("by_op_bytes", {}),
                )
            except Exception as pe:  # noqa: BLE001
                probe_data = {"error": f"{type(pe).__name__}: {pe}"}
        peak_mem = None
        if mem is not None:
            peak_mem = float(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0)
            )
        report = RL.build_report(
            arch=arch,
            cell=cell_name,
            mesh_name=mesh_name,
            chips=chips,
            cost=cost,
            collectives=coll,
            model_flops=model_flops,
            per_device_peak_memory=peak_mem,
        )
        result.update(
            {
                "status": "OK",
                "lower_s": round(t_lower, 1),
                "compile_s": round(t_compile, 1),
                "memory_analysis": {
                    "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                    "output_bytes": getattr(mem, "output_size_in_bytes", None),
                    "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                    "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
                    "peak_bytes_est": peak_mem,
                },
                "cost_analysis": {
                    "flops": cost.get("flops"),
                    "bytes_accessed": cost.get("bytes accessed"),
                    "transcendentals": cost.get("transcendentals"),
                },
                "collectives": {
                    "counts": coll.counts,
                    "by_op_bytes": coll.by_op_bytes,
                    "wire_bytes_per_device": coll.wire_bytes_per_device,
                },
                "roofline": report.as_dict(),
                "probe": probe_data,
                "rules": {k: str(v) for k, v in rules.items()},
            }
        )
        print(
            f"[dryrun] OK {arch} x {cell_name} x {mesh_name}: "
            f"lower {t_lower:.0f}s compile {t_compile:.0f}s | "
            f"flops {report.hlo_flops:.3e} wire/dev {coll.wire_bytes_per_device:.3e} "
            f"peakmem/dev {(peak_mem or 0)/2**30:.2f} GiB | dominant={report.dominant}"
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        result.update({"status": "FAIL", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]})
        print(f"[dryrun] FAIL {arch} x {cell_name} x {mesh_name}: {e}")
    if save:
        _save(result)
    return result


def _opt_tag() -> str:
    """Suffix for artifacts produced under REPRO_OPT_* hillclimb overrides."""
    tags = []
    for k, v in sorted(os.environ.items()):
        if k.startswith("REPRO_OPT_") and v:
            tags.append(f"{k[10:].lower()}{v if v != '1' else ''}")
    return ("__opt_" + "-".join(tags)) if tags else ""


def _save(result: Dict[str, Any]) -> None:
    ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
    name = f"{result['arch']}__{result['cell']}__{result['mesh']}{_opt_tag()}.json"
    (ARTIFACT_DIR / name).write_text(json.dumps(result, indent=2, default=str))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument(
        "--debug-mesh",
        default=None,
        help="comma ints, e.g. 4,4 or 2,4,4 — small mesh for fast iteration",
    )
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the unrolled cost probes (faster; raw costs only)")
    ap.add_argument("--no-save", action="store_true",
                    help="don't write artifacts/dryrun JSON (smoke/CI runs)")
    args = ap.parse_args(argv)
    debug_mesh = (
        tuple(int(x) for x in args.debug_mesh.split(",")) if args.debug_mesh else None
    )

    combos = []
    if args.all:
        from repro.configs.base import SHAPE_SUITE

        for a in ARCH_IDS:
            for c in SHAPE_SUITE:
                combos.append((a, c.name))
    else:
        assert args.arch and args.cell, "--arch and --cell (or --all)"
        combos = [(args.arch, args.cell)]

    failures = 0
    for arch, cell in combos:
        r = run_cell(
            arch, cell, args.multipod, debug_mesh=debug_mesh,
            probe=not args.no_probe and not args.multipod,
            save=not args.no_save,
        )
        if r["status"] == "FAIL":
            failures += 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
