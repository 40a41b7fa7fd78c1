"""The readers of the program's spans, on whole runs on the CPU at a tiny
size: what they read agrees with counts made without the spans, and a
ring that cannot hold the window gives no number."""
from collections import namedtuple

import pytest

from bench import harness, registry
from bench import run as bench_run
from bench.tests.test_bench_faults import CELLS, PEAKS, SEED

READERS = {
    "j2k4k-ingest": ["host_ms_per_step.bulk", "coder_chunks_per_request.bulk"],
    "ct512-ingest-open": ["host_ms_per_step.open", "sched_wait_p95_ms.open"],
    "ct512-read": ["host_ms_per_step.read", "coder_chunks_per_request.read"],
}
ROOT = {"j2k4k-ingest": "serve.step", "ct512-ingest-open": "serve.step",
        "ct512-read": "serve.read"}
# blocks per coder chunk in these runs, so that tiny bands span several chunks
CHUNK_BLOCKS = 2


def chunks_per_container(bucket, levels, lead, chunk_blocks=CHUNK_BLOCKS):
    """Coder chunks of one container of ``lead`` images in ``bucket``: the
    5/3 bands of a power-of-two bucket halve exactly at every level."""
    from repro.codec import rice

    h, w = bucket
    assert h % (1 << levels) == 0 and w % (1 << levels) == 0
    bands = [(h >> levels, w >> levels)]
    for j in range(1, levels + 1):
        bands += [(h >> j, w >> j)] * 3
    return sum(-(-rice.n_blocks(lead * bh * bw) // chunk_blocks) for bh, bw in bands)


@pytest.fixture(scope="module")
def runs():
    """Each cell's run and the tracer's ring as the window left it."""
    from repro import obs
    from repro.codec import rice

    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(rice, "CHUNK_BLOCKS", CHUNK_BLOCKS)
        for name in CELLS:
            bench = registry.benchmark()
            cell = registry.cell(name, bench)
            cfg = dict(registry.config(cell["config"]), **CELLS[name])
            mix = registry.traffic(cell["traffic"])
            if mix["pattern"] == "open":
                mix = dict(mix, rate_per_s=40.0)
            run = harness.Run(cell=name, config=cfg, mix=mix, seed=SEED, seconds=0.6,
                              peaks=PEAKS)
            before = {}

            def start():
                before.update(bench_run.obs_totals())

            def end(run=run):
                after = bench_run.obs_totals()
                run.obs_delta = {k: after[k] - before.get(k, 0.0) for k in after}

            harness.setup_and_window(run, start, end)
            out[name] = (run, obs.tracer.spans(), obs.tracer.total)
    return out


def ring_of(spans, total, capacity=None):
    """A tracer holding ``spans`` as its ring, ``total`` spans recorded."""
    from repro.obs import Tracer

    t = Tracer(capacity or max(len(spans), 1))
    t._spans.extend(spans)
    t._total = total
    return t


def read(monkeypatch, name, run, tracer):
    from repro import obs

    monkeypatch.setattr(obs, "tracer", tracer)
    return registry.metric(name)(run)


def window_roots(run, spans, root):
    n = len(run.records) if root == "serve.read" else int(run.obs_delta["serve.batches"])
    return [s for s in spans if s.name == root and s.parent_id is None][-n:]


def test_the_band_count_gives_the_cells_chunks():
    from repro.codec import rice

    assert rice.CHUNK_BLOCKS == 128
    # a 4096^2 image at 5 levels: 384 + 96 + 24 + 6 + 3 + 1 chunks
    assert chunks_per_container((4096, 4096), 5, 1, rice.CHUNK_BLOCKS) == 514
    # a 4-slice 512^2 container at 5 levels: 24 + 6 + 3 + 3 + 3 + 1
    assert chunks_per_container((512, 512), 5, 4, rice.CHUNK_BLOCKS) == 40


@pytest.mark.parametrize("cell_name,metric", [
    ("j2k4k-ingest", "coder_chunks_per_request.bulk"),
    ("ct512-read", "coder_chunks_per_request.read"),
])
def test_coder_chunks_equal_the_count_from_the_band_shapes(monkeypatch, runs, cell_name,
                                                           metric):
    run, spans, total = runs[cell_name]
    cfg = run.config
    roots = window_roots(run, spans, ROOT[cell_name])
    if cell_name == "ct512-read":
        want = chunks_per_container(cfg["buckets"][0], cfg["levels"], cfg["batch_slots"])
    else:
        per = [chunks_per_container(cfg["buckets"][0], cfg["levels"], len(r.args["uids"]))
               for r in roots]
        want = sum(per) / sum(len(r.args["uids"]) for r in roots)
    assert want > 3 * cfg["levels"] + 1  # some band spans several chunks
    got = read(monkeypatch, metric, run, ring_of(spans, total))
    assert got == want


def test_sched_wait_p95_is_within_the_harness_queue_wait(monkeypatch, runs):
    run, spans, total = runs["ct512-ingest-open"]
    program = read(monkeypatch, "sched_wait_p95_ms.open", run, ring_of(spans, total))
    harness_side = registry.metric("queue_wait_p95_ms.open")(run)
    assert program is not None and 0.0 <= program <= harness_side + 1.0


@pytest.mark.parametrize("cell_name", list(READERS))
def test_host_ms_per_step_lies_between_zero_and_the_mean_root(monkeypatch, runs, cell_name):
    run, spans, total = runs[cell_name]
    roots = window_roots(run, spans, ROOT[cell_name])
    mean_root_ms = sum(r.dur_us for r in roots) / len(roots) / 1e3
    host = read(monkeypatch, READERS[cell_name][0], run, ring_of(spans, total))
    assert 0.0 < host <= mean_root_ms


@pytest.mark.parametrize("cell_name", list(READERS))
def test_every_reader_gives_none_when_the_ring_is_smaller_than_the_window(
        monkeypatch, runs, cell_name):
    run, spans, total = runs[cell_name]
    roots = window_roots(run, spans, ROOT[cell_name])
    # the last span to end before the window's first root began
    before = max(i for i, s in enumerate(spans) if s.ts_us + s.dur_us <= roots[0].ts_us)
    for name in READERS[cell_name]:
        assert read(monkeypatch, name, run, ring_of(spans, total)) is not None
        # the last root alone: fewer roots than the window served
        assert read(monkeypatch, name, run, ring_of(spans[-1:], total)) is None
        # every root and all it holds, in a ring that has wrapped
        assert read(monkeypatch, name, run, ring_of(spans[before:], total)) is not None
        # every root, but the first root's earliest span evicted
        assert read(monkeypatch, name, run, ring_of(spans[before + 1:], total)) is None


def test_readers_give_none_for_spans_without_parent_ids(monkeypatch, runs):
    """A program whose spans carry no ids (what the readers meet in a
    checkout from before the ids) gives no number and no error."""
    Old = namedtuple("Old", "name cat ts_us dur_us tid args")
    for cell_name, names in READERS.items():
        run, spans, total = runs[cell_name]
        old = [Old(*s[:6]) for s in spans]
        for name in names:
            assert read(monkeypatch, name, run, ring_of(old, total)) is None
