#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate the system
sustains without a growing backlog.

    python bench/sweep.py --workload ct512-ingest-open --seed <n> \\
        --seconds 20 --rates 6,8,10,12,14

One process: the pool and the system are built and warmed up once, then
the cell's traffic runs at each rate for ``--seconds``.  Each rate prints
one JSON line: requests due, the rate served, the 95th percentile from
due time, and the drain (how long after the last arrival the queue took
to empty).  A rate is sustained where the drain stays within about one
batch and the served rate keeps up with the offered one.  The cell's
traffic file then takes about four fifths of the knee, as a number; the
benchmark's own runs never search for a rate.

``--schedule-seeds 1,2,3`` replays each of those arrival schedules at
each rate instead of the mix's own, with the same pool and engine: that
shows whether the mix's schedule reads a typical tail.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import data, harness, registry, work  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True, help="comma-separated arrivals per second")
    ap.add_argument("--schedule-seeds", default=None,
                    help="comma-separated arrival schedules to replay at each rate "
                         "(default: the mix's own)")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 2
    bench = registry.benchmark()
    cell = registry.cell(args.workload, bench)
    config = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    if mix["pattern"] != "open":
        print("sweep: the cell's traffic is not open-loop", file=sys.stderr)
        return 2
    pattern = registry.pattern(mix["pattern"])
    pool = data.make_pool(config, args.seed)
    engine = registry.system(config["system"]).build(config)
    run = harness.Run(cell=cell["name"], config=config, mix=mix, seed=args.seed,
                      seconds=args.seconds)
    state = pattern.setup(run, engine, pool)
    schedules = [int(s) for s in (args.schedule_seeds or str(mix["schedule_seed"])).split(",")]
    for rate, schedule in ((float(r), s) for r in args.rates.split(",") for s in schedules):
        run = harness.Run(cell=cell["name"], config=config,
                          mix=dict(mix, rate_per_s=rate, schedule_seed=schedule),
                          seed=args.seed, seconds=args.seconds)
        pattern.window(run, state)
        done = run.completed
        last_due = max(r.due for r in run.records)
        lat = [(r.finished - r.due) * 1e3 if r.answered else float("inf") for r in run.records]
        print(json.dumps({
            "rate_per_s": rate,
            "schedule_seed": schedule,
            "due": len(run.records),
            "answered": len(done),
            "served_per_s": len(done) / run.window_s,
            "p50_ms": sorted(lat)[len(lat) // 2],
            "p95_ms": work.p95(lat),
            "drain_s": run.window_s - last_due,
            "window_s": run.window_s,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
