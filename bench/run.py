#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); ``registry.py`` says where the rest is
found.  The run builds the image pool from the seed, builds the system
under test, warms up every shape the window uses (all of it set-up),
measures for ``--seconds``, and then holds the window's answers to the
plain reference.  It prints one
JSON object as its last line of standard output::

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"], "checks"}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of the window and reports the per-layer metrics, with
``busy_s`` / ``window_s`` in ``device`` and the trace's ``breakdown``.
The numbers compared, each with its limit, come last: in ``checks`` and
as the last lines of standard error.

With no TPU, or fewer chips than the cell asks for, it prints no result
and exits 2.  ``--control lsb`` runs the control of the lossless
guarantee (the system is given the samples with their lowest bit
cleared) and must come out not correct; the benchmark's own runs never
pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import harness, registry, trace  # noqa: E402

# the compile cache lives at one fixed path inside the checkout, so only
# the first run of a cell in a checkout compiles
CACHE_DIR = CHECKOUT / ".jax_cache"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("none", "lsb"), default="none")
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench: {msg}; no result", file=sys.stderr)
    return 2


def device_facts(chips: int):
    """The accelerator JAX found, or an error message."""
    import jax

    devices = jax.devices()
    facts = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if facts["platform"] != "tpu":
        return facts, f"JAX found no TPU (platform {facts['platform']!r})"
    if facts["count"] < chips:
        return facts, f"the cell asks for {chips} chips, JAX found {facts['count']}"
    return facts, None


def obs_totals() -> dict:
    """The program's counters the window's deltas are taken from."""
    from repro import obs

    snap = obs.registry.snapshot()
    out = {"serve.requests_served": 0.0, "serve.batches": 0.0, "degrades": 0.0,
           "faults": float(obs.events.counts().get("FaultEvent", 0))}
    for key, value in snap.items():
        if key in ("serve.requests_served", "serve.batches"):
            out[key] = float(value)
        elif key.startswith(("kernels.degrades", "serve.encode_degrades")):
            out["degrades"] += float(value)
        elif key.startswith("kernels.dispatch{"):
            resolved = key.split('resolved="', 1)[1].split('"', 1)[0]
            name = f"dispatch.{resolved}"
            out[name] = out.get(name, 0.0) + float(value)
    return out


def run_cell(cell: dict, config: dict, mix: dict, bench: dict, *, seed: int,
             seconds: float, traced: bool, control: str, device: dict,
             peaks: dict, root: Path = registry.ROOT) -> tuple:
    """Set-up, window, check and metrics of one run on the devices JAX
    holds; returns ``(window facts, result)``.  Looks for no chip.
    ``root`` is the directory the cell's patterns, systems, sample models
    and metric readers are found in."""
    import jax

    readers = {
        m["name"]: registry.metric(m["name"], root)
        for m in registry.metrics_for(cell["name"], bench, traced)
    }
    run = harness.Run(
        cell=cell["name"], config=config, mix=mix, seed=seed,
        seconds=seconds, control=control, root=root, peaks=peaks,
    )
    compiles = []
    counting = [False]

    def on_compile(name, *_a, **_k):
        if counting[0] and name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    before = {}

    def window_start():
        run.setup_s = time.perf_counter() - T_START
        before.update(obs_totals())
        counting[0] = True
        if trace_dir:
            jax.profiler.start_trace(trace_dir, profiler_options=trace.profiler_options())

    def stop_trace():
        if "trace_stop_s" not in run.extra:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            run.extra["trace_stop_s"] = time.perf_counter() - t

    if trace_dir:
        run.stop_trace = stop_trace

    def window_end():
        counting[0] = False
        if trace_dir:
            stop_trace()
        after = obs_totals()
        run.obs_delta = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}

    device = dict(device)
    try:
        pool = harness.setup_and_window(run, window_start, window_end)
        run.window_compiles = len(compiles)
        stats = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        t = time.perf_counter()
        checks = harness.check(run, pool)
        check_s = time.perf_counter() - t
        breakdown = None
        if trace_dir:
            t = time.perf_counter()
            run.trace = trace.reduce(trace.load_events(trace_dir))
            run.extra["trace_read_s"] = time.perf_counter() - t
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            breakdown = {k: run.trace[k] for k in ("device_ops", "idle_gaps")}
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in registry.metrics_for(cell["name"], bench, traced):
        value = readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    lateness = [r.submitted - r.due for r in run.records if r.submitted == r.submitted]
    window = {
        "phase": "window",
        "setup_parts_s": run.setup_parts,
        "window_s": run.window_s,
        "requests": len(run.records),
        "compiles_in_window": run.window_compiles,
        "degrades_in_window": run.obs_delta.get("degrades", 0.0),
        "faults_in_window": run.obs_delta.get("faults", 0.0),
        "dispatch_in_window": {
            k: v for k, v in run.obs_delta.items() if k.startswith("dispatch.")
        },
        "generator_late_max_s": max(lateness, default=0.0),
        "check_s": check_s,
        **{k: run.extra[k] for k in ("trace_stop_s", "trace_read_s") if k in run.extra},
        "control": run.control,
    }
    result = {
        "correct": harness.passed(checks),
        "attempted": len(run.records),
        "failed": sum(1 for r in run.records if not r.answered),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return window, result


def main(argv=None) -> int:
    args = parse(argv)
    bench = registry.benchmark()
    cell = registry.cell(args.workload, bench)
    config = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device, err = device_facts(int(cell["chips"]))
    if err:
        return fail(err)
    try:
        peaks = registry.peaks(device["kind"])
    except KeyError as e:
        return fail(str(e))
    from repro.launch import compile_cache

    compile_cache.enable()
    window, result = run_cell(
        cell, config, mix, bench, seed=args.seed, seconds=args.seconds,
        traced=bool(args.trace), control=args.control, device=device, peaks=peaks,
    )
    print(json.dumps(window), flush=True)
    print(f"bench: window {json.dumps(window)}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
