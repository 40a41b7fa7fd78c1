"""A standing backlog of ``backlog`` requests through ``submit``/``step``.

The window closes at the first whole pass over the pool completed after
``seconds``, so every run ingests the same mix of shapes and the rate
counts whole requests over all the time they took.
"""
from typing import Dict

from bench import harness, traffic
from bench.harness import CLOCK, Record, annotate

# how long the window may run past ``seconds`` to finish its pass over
# the pool, before it closes anyway (an engine that stopped serving)
CYCLE_GRACE_S = 60.0


setup = harness.ingest_setup


def window(run, state) -> None:
    from repro.serve import TransformRequest

    engine, pool = state
    backlog = int(run.mix["backlog"])
    order = traffic.pool_order(len(pool), run.seed)
    queued: Dict[int, Record] = {}
    records: Dict[int, Record] = {}
    uid = 0
    t0 = CLOCK()
    end = t0
    with annotate("bench.window"):
        while True:
            with annotate("bench.submit"):
                while engine.scheduler.pending() < backlog:
                    i = next(order)
                    rec = Record(uid, i, pool[i].shape, due=CLOCK() - t0)
                    rec.submitted = rec.due
                    engine.submit(TransformRequest(
                        uid=uid, image=harness.system_image(pool[i], run.control)))
                    queued[uid] = rec
                    uid += 1
            started = CLOCK()
            elapsed = started - t0
            if elapsed >= run.seconds and (
                len(records) % len(pool) == 0 or elapsed >= run.seconds + CYCLE_GRACE_S
            ):
                break
            with annotate("bench.step"):
                done = engine.step()
            for req in done:
                records[req.uid] = queued.pop(req.uid)
            end = harness.finish(done, records, started, t0)
    run.window_s = end - t0
    # a request the engine dropped is neither served nor still queued: it
    # never comes, and is one of the window's answers missing
    lost = len(queued) - engine.scheduler.pending()
    for rec in sorted(queued.values(), key=lambda r: r.uid)[:max(lost, 0)]:
        rec.error = "lost"
        records[rec.uid] = rec
    run.records = [records[u] for u in sorted(records)]
