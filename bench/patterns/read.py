"""One closed-loop reader of full-fidelity slices through
``ProgressiveServeRoute.full``.

Set-up ingests the series (the first ``series_slices`` pool images)
through the engine and files each container in the route, then reads
once per container: that compiles every decode shape the window meets
(the coder's chunk shapes depend on the coded lengths).  Reads that
start inside the window count, and it closes when the last completes.
"""
from typing import List

from bench import harness, traffic
from bench.harness import CLOCK, Record, annotate


def setup(run, engine, pool):
    from repro.serve import ProgressiveServeRoute, TransformRequest

    t = CLOCK()
    engine.warmup()
    run.setup_parts["compile_s"] = CLOCK() - t
    route = ProgressiveServeRoute()
    series = pool[: run.config["series_slices"]]
    t = CLOCK()
    done = engine.run([
        TransformRequest(uid=i, image=harness.system_image(img, run.control))
        for i, img in enumerate(series)
    ])
    for req in done:
        if req.error is not None:
            raise RuntimeError(f"series ingest failed for slice {req.uid}: {req.error!r}")
        req.pyramid = None
        route.store(req)
    # each slice's share of its container's bytes, for the roofline
    run.extra["coded_bytes_per_slice"] = {
        req.uid: len(req.encoded) / engine.batch_slots for req in done
    }
    run.setup_parts["series_ingest_s"] = CLOCK() - t
    t = CLOCK()
    for first in range(0, len(series), engine.batch_slots):
        route.full(first)
    run.setup_parts["decode_warmup_s"] = CLOCK() - t
    return route, series


def window(run, state) -> None:
    """Where the run is traced and the mix sets ``trace_seconds``, the
    ``bench.window`` annotation and the trace cover only the reads that
    start in that first part: the decoder's loop writes so many device
    events that a longer trace overflows the profiler's buffers."""
    route, series = state
    order = traffic.read_order(len(series), run.seed)
    records: List[Record] = []
    traced = run.mix.get("trace_seconds") if run.stop_trace else None
    t0 = CLOCK()

    def reads(until: float) -> None:
        while CLOCK() - t0 < until:
            i = next(order)
            rec = Record(len(records), i, series[i].shape, due=CLOCK() - t0)
            rec.submitted = rec.started = rec.due
            with annotate("bench.full"):
                try:
                    rec.delivered = route.full(i)
                except Exception as e:  # noqa: BLE001 - a failed read is an answer missing
                    rec.error = type(e).__name__
            rec.finished = CLOCK() - t0
            records.append(rec)

    with annotate("bench.window"):
        reads(min(traced or run.seconds, run.seconds))
    if traced:
        run.stop_trace()
        run.traced_until = records[-1].finished if records else 0.0
        reads(run.seconds)
    run.window_s = records[-1].finished if records else CLOCK() - t0
    run.records = records
