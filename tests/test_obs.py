"""Tests for repro.obs: metrics, events, tracing, subsystem wiring.

Covers the histogram quantile contract (bucketed p50/p99 must bracket
the exact numpy percentile on adversarial distributions — property
tested), counter thread-safety under concurrent hammering and the serve
retry path, the degrade-counting fix (every degrade counts, the warning
still fires once), warning-site consolidation (categories preserved),
and the end-to-end acceptance check: one seeded run reports live
metrics from all five subsystems plus a valid Chrome trace.
"""
from __future__ import annotations

import json
import threading
import warnings

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover - shim container
    from hypothesis_shim import given, settings
    from hypothesis_shim import strategies as st

from repro import obs
from repro.obs.metrics import Histogram, MetricRegistry


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Each test starts from empty process-wide metrics/events/spans."""
    obs.set_enabled(True)
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# Metric registry basics.
# ---------------------------------------------------------------------------


def test_counter_monotonic_and_labelled_series_are_distinct():
    a = obs.counter("t.hits", route="a")
    b = obs.counter("t.hits", route="b")
    a.inc()
    a.inc(2.0)
    b.inc()
    assert a.value == 3.0 and b.value == 1.0
    assert obs.counter("t.hits", route="a") is a  # get-or-create
    with pytest.raises(ValueError):
        a.inc(-1)


def test_gauge_set_and_add():
    g = obs.gauge("t.depth")
    g.set(5)
    g.add(-2)
    assert g.value == 3.0


def test_metric_kind_mismatch_raises():
    obs.counter("t.thing")
    with pytest.raises(TypeError, match="already registered"):
        obs.gauge("t.thing")


def test_snapshot_keys_and_histogram_summary():
    obs.counter("t.c", k="v").inc()
    h = obs.histogram("t.h")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    snap = obs.registry.snapshot()
    assert snap['t.c{k="v"}'] == 1.0
    s = snap["t.h"]
    assert s["count"] == 3 and s["sum"] == 6.0 and s["min"] == 1.0
    assert s["max"] == 3.0 and "p50" in s and "p99" in s


def test_prometheus_exposition_shape():
    obs.counter("t.total", op="enc").inc(4)
    obs.gauge("t.depth").set(2)
    h = obs.histogram("t.lat", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    text = obs.render_prometheus()
    assert '# TYPE t_total counter' in text
    assert 't_total{op="enc"} 4' in text
    assert '# TYPE t_depth gauge' in text
    assert '# TYPE t_lat histogram' in text
    # cumulative bucket counts, then the +Inf bucket == count
    assert 't_lat_bucket{le="1"} 1' in text
    assert 't_lat_bucket{le="10"} 2' in text
    assert 't_lat_bucket{le="+Inf"} 3' in text
    assert 't_lat_sum 55.5' in text
    assert 't_lat_count 3' in text


def test_disabled_flag_makes_instruments_no_ops():
    c = obs.counter("t.c")
    h = obs.histogram("t.h")
    with obs.disabled():
        c.inc(100)
        h.observe(1.0)
        obs.emit(obs.Event(subsystem="t"))
        with obs.span("t.s", subsystem="t"):
            pass
    assert c.value == 0.0
    assert h.count == 0
    assert obs.events.total == 0
    assert obs.tracer.total == 0


# ---------------------------------------------------------------------------
# Histogram quantile math: bucketed estimates must bracket the exact
# sample percentile (property-tested on adversarial distributions).
# ---------------------------------------------------------------------------


def _adversarial_data(kind: str, seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "lognormal":  # heavy right tail across many decades
        return rng.lognormal(0.0, 3.0, n)
    if kind == "constant":  # every observation in ONE bucket
        return np.full(n, 7.3)
    if kind == "bimodal":  # two spikes five decades apart
        return np.where(rng.integers(2, size=n) == 0, 1e-2, 1e3).astype(float)
    if kind == "uniform-wide":
        return rng.uniform(1e-3, 1e6, n)
    if kind == "tiny":  # below the smallest default bucket bound
        return rng.uniform(1e-5, 5e-4, n)
    raise AssertionError(kind)


@settings(max_examples=30)
@given(
    kind=st.sampled_from(
        ["lognormal", "constant", "bimodal", "uniform-wide", "tiny"]
    ),
    seed=st.integers(min_value=0, max_value=2**16),
    n=st.integers(min_value=1, max_value=400),
)
def test_quantile_bounds_bracket_exact_percentiles(kind, seed, n):
    data = _adversarial_data(kind, seed, n)
    reg = MetricRegistry()
    h = reg.histogram("q.h")
    for v in data:
        h.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        lo, hi = h.quantile_bounds(q)
        exact = float(np.percentile(data, q * 100))
        # numpy interpolates between order statistics; the bucketed
        # bounds cover the nearest-rank order statistic, so allow the
        # bounds to be checked against the un-interpolated quantile too
        nearest = float(np.sort(data)[min(n - 1, max(0, int(np.ceil(q * n)) - 1))])
        assert lo <= nearest <= hi, (kind, q, lo, nearest, hi)
        assert lo <= max(exact, lo) and min(exact, hi) <= hi
        est = h.quantile(q)
        assert lo <= est <= hi, (kind, q, lo, est, hi)


def test_quantile_estimate_brackets_numpy_on_large_sample():
    data = np.random.default_rng(0).lognormal(1.0, 2.0, 5000)
    h = Histogram("q.h", (), threading.Lock())
    for v in data:
        h.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        lo, hi = h.quantile_bounds(q)
        assert lo <= float(np.percentile(data, q * 100)) <= hi


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError, match="ascend"):
        Histogram("bad", (), threading.Lock(), buckets=(3.0, 1.0))


def test_empty_histogram_quantiles_are_zero():
    h = Histogram("e", (), threading.Lock())
    assert h.quantile(0.5) == 0.0
    assert h.quantile_bounds(0.99) == (0.0, 0.0)


# ---------------------------------------------------------------------------
# Thread safety: counters hammered concurrently, and via the serve
# retry path (worker threads submitting through a faulted engine).
# ---------------------------------------------------------------------------


def test_counter_thread_safety_under_contention():
    c = obs.counter("t.contended")
    h = obs.histogram("t.contended_h")
    n_threads, n_incs = 8, 2000

    def hammer():
        for i in range(n_incs):
            c.inc()
            h.observe(float(i % 50))

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == float(n_threads * n_incs)
    assert h.count == n_threads * n_incs


def test_serve_retry_path_counts_attempts_and_events():
    from repro.resilience import inject
    from repro.resilience.errors import RetryWarning
    from repro.serve.engine import TransformRequest, WaveletServeEngine

    eng = WaveletServeEngine(
        height=16, width=16, levels=1, batch_slots=2, retry_backoff_s=0.001
    )
    img = np.random.default_rng(1).integers(-100, 100, (16, 16), np.int32)
    eng.submit(TransformRequest(uid=1, image=img))
    with inject.armed("serve.transform", times=1):
        with pytest.warns(RetryWarning, match="retrying"):
            done = eng.step()
    assert done[0].done
    assert obs.registry.counter("serve.retry_attempts").value == 1.0
    assert len(obs.events.query(obs.RetryEvent)) == 1
    # the retry that then succeeded is a heal
    heals = obs.events.query(obs.HealEvent, subsystem="serve")
    assert len(heals) == 1 and heals[0].mechanism == "retry"


@pytest.mark.parametrize("bucket", [(8, 16, 16), (16, 16)])
def test_serve_transform_span_names_a_volume_plan(bucket):
    """A volume bucket's ``serve.transform`` span carries ``plan`` (its
    per-level paths, worked out once at build), each step counts one
    ``serve.transform_plan{plan=...}`` and the device program is
    ``jit_transform_3d``; a 2-D bucket's span, counters and program name
    are as they were."""
    from repro.kernels import fused3d
    from repro.serve.engine import TransformRequest, WaveletServeEngine
    from repro.serve.executor import plan_label

    eng = WaveletServeEngine(
        buckets=[bucket], batch_slots=1, levels=2, backend="interpret"
    )
    eng.warmup()
    rng = np.random.default_rng(2)
    done = eng.run([
        TransformRequest(uid=i, image=rng.integers(-100, 100, bucket, np.int32))
        for i in range(3)
    ])
    assert all(r.done for r in done)
    spans = obs.tracer.spans(name="serve.transform")
    assert len(spans) == 3
    plans = {
        k: v for k, v in obs.registry.snapshot().items()
        if k.startswith("serve.transform_plan")
    }
    (program,) = eng.executor._cache.values()
    name = program.as_text().split("\n", 1)[0]
    label = "x".join(str(s) for s in bucket)
    if len(bucket) == 3:
        plan = plan_label(fused3d.plan_3d_levels(bucket, 2, "interpret"))
        assert plan == "whole-interpret×2"
        assert all(s.args == {"bucket": label, "plan": plan} for s in spans)
        assert plans == {f'serve.transform_plan{{plan="{plan}"}}': 3.0}
        assert name.startswith("HloModule jit_transform_3d")
    else:
        assert all(s.args == {"bucket": label} for s in spans)
        assert plans == {}
        assert name.startswith("HloModule jit_transform,")


def test_plan_label_runs_levels_finest_first():
    from repro.serve.executor import plan_label

    assert plan_label(("slab-pallas",) * 3 + ("whole-pallas",) * 2) == (
        "slab-pallas×3,whole-pallas×2"
    )
    assert plan_label(("xla",) * 5) == "xla×5"


# ---------------------------------------------------------------------------
# Satellite 1: every degrade counts; the warning still fires once.
# ---------------------------------------------------------------------------


def test_repeat_degrades_count_every_occurrence_warn_once():
    from repro.kernels import backend

    reason = "test-only: repeat-degrade counting"  # unique key this run
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in range(5):
            backend.note_degrade("pallas", "xla", reason)
    ours = [x for x in w if reason in str(x.message)]
    assert len(ours) == 1, "dedupe must keep the warning once-per-key"
    assert isinstance(ours[0].message, backend.BackendDegradeWarning)
    c = obs.registry.counter(
        "kernels.degrades", requested="pallas", resolved="xla"
    )
    assert c.value == 5.0, "every degrade occurrence must count"
    evs = [
        e for e in obs.events.query(obs.DegradeEvent, subsystem="kernels")
        if e.reason == reason
    ]
    assert len(evs) == 5


# ---------------------------------------------------------------------------
# Satellite 2: consolidated warning sites keep their categories.
# ---------------------------------------------------------------------------


def test_encode_degrade_warning_category_and_event():
    from repro.resilience import inject
    from repro.resilience.errors import ResilienceWarning
    from repro.serve.engine import TransformRequest, WaveletServeEngine

    eng = WaveletServeEngine(
        height=16, width=16, levels=1, batch_slots=2, encode_response=True
    )
    img = np.random.default_rng(2).integers(-100, 100, (16, 16), np.int32)
    eng.submit(TransformRequest(uid=7, image=img))
    with inject.armed("serve.encode_batch", times=1):
        with pytest.warns(ResilienceWarning, match="degrading to per-request"):
            done = eng.step()
    assert done[0].encoded is not None  # per-request fallback served bytes
    degr = obs.events.query(obs.DegradeEvent, subsystem="serve")
    assert len(degr) == 1 and degr[0].requested == "batch-encode"
    assert obs.registry.counter("serve.encode_degrades").value == 1.0


def test_warn_event_emits_both_event_and_warning():
    with pytest.warns(RuntimeWarning, match="both channels"):
        obs.warn_event(
            obs.FaultEvent(subsystem="serve", error="X", site="t"),
            RuntimeWarning("both channels"),
        )
    assert len(obs.events.query(obs.FaultEvent)) == 1


# ---------------------------------------------------------------------------
# Event log semantics.
# ---------------------------------------------------------------------------


def test_event_ring_bounded_total_unbounded():
    log = obs.EventLog(capacity=8)
    for i in range(20):
        log.emit(obs.Event(subsystem="t", detail=str(i)))
    assert len(log) == 8
    assert log.total == 20
    assert [e.detail for e in log][0] == "12"  # oldest 12 fell off


def test_event_query_filters_and_to_dict():
    obs.emit(obs.DegradeEvent(subsystem="kernels", requested="a"))
    obs.emit(obs.FaultEvent(subsystem="serve", error="E", site="s"))
    assert len(obs.events.query(obs.DegradeEvent)) == 1
    assert len(obs.events.query(subsystem="serve")) == 1
    d = obs.events.query(obs.FaultEvent)[0].to_dict()
    assert d["kind"] == "FaultEvent" and d["error"] == "E"
    assert obs.events.counts() == {"DegradeEvent": 1, "FaultEvent": 1}


# ---------------------------------------------------------------------------
# Tracing and Chrome-trace export.
# ---------------------------------------------------------------------------


def test_span_records_duration_and_attrs():
    with obs.span("t.work", subsystem="serve", bucket="16x16"):
        pass
    (s,) = obs.tracer.spans(name="t.work")
    assert s.cat == "serve" and s.dur_us >= 0.0
    assert s.args == {"bucket": "16x16"}


def test_chrome_trace_is_valid_and_loadable_shape(tmp_path):
    with obs.span("a", subsystem="codec"):
        with obs.span("b", subsystem="codec"):
            pass
    path = tmp_path / "trace.json"
    obs.write_chrome_trace(path)
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == 2
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X"
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(ev)
    # inner span nests inside the outer on the same lane
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    a, b = by_name["a"], by_name["b"]
    assert a["ts"] <= b["ts"] and b["ts"] + b["dur"] <= a["ts"] + a["dur"] + 1


def test_span_and_parent_ids_nest_three_deep_and_stay_apart_per_thread():
    barrier = threading.Barrier(2)

    def nest(tag):
        with obs.span(f"{tag}.a"):
            barrier.wait(timeout=10)  # both threads hold a span open at once
            with obs.span(f"{tag}.b"):
                with obs.span(f"{tag}.c"):
                    barrier.wait(timeout=10)

    threads = [threading.Thread(target=nest, args=(t,)) for t in ("x", "y")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    by = {s.name: s for s in obs.tracer.spans()}
    assert len(by) == 6 and len({s.span_id for s in by.values()}) == 6
    for tag in ("x", "y"):
        a, b, c = by[f"{tag}.a"], by[f"{tag}.b"], by[f"{tag}.c"]
        assert a.parent_id is None
        assert b.parent_id == a.span_id and c.parent_id == b.span_id
        assert a.tid == b.tid == c.tid
    assert by["x.a"].tid != by["y.a"].tid
    events = {e["name"]: e["args"] for e in obs.export_chrome_trace()["traceEvents"]}
    assert events["x.c"]["parent_id"] == events["x.b"]["span_id"]


def test_wait_lands_on_the_innermost_open_span():
    with obs.span("t.outer") as outer:
        obs.tracer.add_wait(0.5)
        with obs.span("t.inner", chunks=3) as inner:
            obs.tracer.add_wait(0.25)
            with obs.waiting():
                pass
            inner["blocks"] = 7
        obs.tracer.add_wait(0.5)
    obs.tracer.add_wait(9.0)  # no span open: dropped
    (o,) = obs.tracer.spans(name="t.outer")
    (i,) = obs.tracer.spans(name="t.inner")
    assert outer is o.args and o.args == {"wait_s": 1.0}
    assert 0.25 <= i.args["wait_s"] < 0.3 and inner is i.args
    assert i.args["chunks"] == 3 and i.args["blocks"] == 7


def test_disabled_tracer_records_nothing_and_yields_usable_attrs():
    with obs.disabled():
        with obs.span("t.off", subsystem="t", n=2) as attrs:
            attrs["chunks"] = 4
            obs.tracer.add_wait(1.0)
            with obs.waiting():
                pass
    assert attrs == {"n": 2, "chunks": 4}
    assert obs.tracer.total == 0 and len(obs.tracer) == 0


def test_a_span_imports_nothing_once_the_first_has_run():
    import builtins

    with obs.span("t.first"):
        pass
    calls = []
    real = builtins.__import__

    def counting(*a, **k):
        calls.append(a[0])
        return real(*a, **k)

    builtins.__import__ = counting
    try:
        for _ in range(100):
            with obs.span("t.again", subsystem="t"):
                pass
    finally:
        builtins.__import__ = real
    assert calls == []
    assert len(obs.tracer.spans(name="t.again")) == 100


def test_span_exceptions_still_record():
    with pytest.raises(RuntimeError):
        with obs.span("t.fail", subsystem="serve"):
            raise RuntimeError("boom")
    assert len(obs.tracer.spans(name="t.fail")) == 1


# ---------------------------------------------------------------------------
# End-to-end acceptance: one seeded run covers all five subsystems.
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_all_five_subsystems_report_in_one_run(tmp_path):
    import jax

    from repro.ckpt.checkpoint import CheckpointManager
    from repro.serve.engine import TransformRequest, WaveletServeEngine

    from repro import kernels as K

    rng = np.random.default_rng(0)
    # a direct (un-jitted) kernel call records the kernels-subsystem span
    K.dwt_fwd_2d_multi(
        rng.integers(-100, 100, (1, 16, 16), dtype=np.int32)[:], levels=1
    )
    eng = WaveletServeEngine(
        buckets=[(32, 32)], batch_slots=4, levels=2, encode_response=True
    )
    done = eng.run([
        TransformRequest(
            uid=i, image=rng.integers(-100, 100, (32, 32), dtype=np.int32)
        )
        for i in range(6)
    ])
    assert all(r.done for r in done)

    mgr = CheckpointManager(tmp_path / "ckpt", codec="wz-rice")
    mgr.save(0, {"w": rng.normal(size=(16, 16)).astype(np.float32)})
    mgr.restore()

    if len(jax.devices()) >= 2:
        from jax.sharding import Mesh

        from repro.kernels import sharded

        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        x = rng.integers(-50, 50, (16, 32), dtype=np.int32)
        sharded.dwt_inv_2d_sharded(
            sharded.dwt_fwd_2d_sharded(jax.numpy.asarray(x), mesh, levels=1),
            mesh, timeout_s=30.0,
        )
        want = {"kernels", "codec", "serve", "ckpt", "collectives"}
    else:  # single-device CI lane: no collectives to observe
        want = {"kernels", "codec", "serve", "ckpt"}

    assert want <= obs.subsystems(), obs.subsystems()
    snap = obs.snapshot()
    assert snap["events"]["total"] > 0
    cats = {e["cat"] for e in obs.export_chrome_trace()["traceEvents"]}
    assert want <= cats, cats
