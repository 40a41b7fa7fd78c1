"""Benchmark harness — one module per paper table/figure + framework-level
benches. Prints ``name,value,notes`` CSV; the ``kernels`` bench also
writes a machine-readable ``BENCH_kernels.json`` (interpret vs compiled,
per-level and 2D timings) so the perf trajectory is tracked across PRs.

    PYTHONPATH=src python -m benchmarks.run [--only table2,fig5,...]
                                           [--json-out BENCH_kernels.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from pathlib import Path

from repro.launch import compile_cache

ALL = ["table2", "table3", "fig5", "gradsync", "ckpt", "roofline", "kernels"]

REPO_ROOT = Path(__file__).resolve().parents[1]


def _load(name: str):
    if name == "table2":
        from benchmarks import table2_opcounts as m
    elif name == "table3":
        from benchmarks import table3_timing as m
    elif name == "fig5":
        from benchmarks import fig5_lossless as m
    elif name == "gradsync":
        from benchmarks import grad_compression as m
    elif name == "ckpt":
        from benchmarks import ckpt_compression as m
    elif name == "roofline":
        from benchmarks import roofline_table as m
    elif name == "kernels":
        from benchmarks import kernels_bench as m
    else:
        raise KeyError(name)
    return m


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset")
    ap.add_argument(
        "--json-out",
        default=str(REPO_ROOT / "BENCH_kernels.json"),
        help="where the kernels bench writes its JSON payload",
    )
    args = ap.parse_args(argv)
    names = args.only.split(",") if args.only else ALL
    compile_cache.enable()

    print("name,value,notes")
    failures = 0
    for name in names:
        try:
            mod = _load(name)
            if hasattr(mod, "run_json"):
                rows, payload = mod.run_json()
                out = Path(args.json_out)
                out.write_text(json.dumps(payload, indent=2) + "\n")
                rows.append((f"{name}.json", out.name, "machine-readable payload"))
            else:
                rows = mod.run()
            for key, value, notes in rows:
                print(f"{key},{value},{notes}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name}.ERROR,{type(e).__name__},{e}")
            traceback.print_exc(file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
