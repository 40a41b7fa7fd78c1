"""Open-loop single arrivals through ``submit``/``step``.

Requests are due on the mix's replayed schedule; the queue then drains,
and latency runs from each request's due time.
"""
import time

from bench import harness, traffic
from bench.harness import CLOCK, Record, annotate


setup = harness.ingest_setup


def window(run, state) -> None:
    from repro.resilience.errors import LoadShedError
    from repro.serve import TransformRequest

    engine, pool = state
    plan = traffic.arrivals(run.mix, len(pool), run.seed, run.seconds)
    records = {
        uid: Record(uid, i, pool[i].shape, due=due) for uid, (due, i) in enumerate(plan)
    }
    nxt = 0
    t0 = CLOCK()
    end = t0
    with annotate("bench.window"):
        while nxt < len(plan) or engine.scheduler.pending():
            now = CLOCK() - t0
            if nxt < len(plan) and plan[nxt][0] <= now:
                with annotate("bench.submit"):
                    while nxt < len(plan) and plan[nxt][0] <= now:
                        rec = records[nxt]
                        rec.submitted = CLOCK() - t0
                        try:
                            engine.submit(TransformRequest(
                                uid=nxt,
                                image=harness.system_image(pool[rec.pool_index], run.control)))
                        except LoadShedError:
                            rec.error = "LoadShedError"
                        nxt += 1
            elif engine.scheduler.pending():
                started = CLOCK()
                with annotate("bench.step"):
                    done = engine.step()
                end = harness.finish(done, records, started, t0)
            else:
                with annotate("bench.wait"):
                    time.sleep(max(0.0, plan[nxt][0] - (CLOCK() - t0)))
    run.window_s = end - t0
    run.records = [records[u] for u in sorted(records)]
