"""A whole run on the CPU at a tiny size, looking for no chip: sound runs
come out correct; the control and each planted fault come out not correct."""
import numpy as np
import pytest

from bench import harness, registry
from bench import run as bench_run

SEED = 2**31 + 77
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"hbm_bytes_per_s": 819e9}

CELLS = {
    "j2k4k-ingest": dict(buckets=[[64, 64]], levels=3,
                         request_shapes=[[64, 64], [64, 52], [47, 62], [40, 64]]),
    "ct512-ingest-open": dict(buckets=[[32, 32]], levels=2, request_shapes=[[32, 32]],
                              pool_size=8, series_slices=8),
    "ct512-read": dict(buckets=[[32, 32]], levels=2, request_shapes=[[32, 32]],
                       pool_size=8, series_slices=8),
}


def _run(cell_name, control="none", seconds=0.6):
    bench = registry.benchmark()
    cell = registry.cell(cell_name, bench)
    cfg = dict(registry.config(cell["config"]), **CELLS[cell_name])
    mix = registry.traffic(cell["traffic"])
    if mix["pattern"] == "open":
        mix = dict(mix, rate_per_s=40.0)
    _, result = bench_run.run_cell(
        cell, cfg, mix, bench, seed=SEED, seconds=seconds, traced=False,
        control=control, device=DEVICE, peaks=PEAKS,
    )
    return result


def _altered_pyramid(monkeypatch):
    from repro.serve.executor import TransformExecutor

    real = TransformExecutor.run

    def run(self, fn, batch, key):
        out = real(self, fn, batch, key)
        return out._replace(ll=out.ll.at[0, 0, 0].add(1))

    monkeypatch.setattr(TransformExecutor, "run", run)


def _half_batch(monkeypatch):
    from repro.serve.scheduler import BucketScheduler

    real = BucketScheduler.next_batch

    def next_batch(self, slots):
        bucket, batch = real(self, slots)
        return bucket, batch[: len(batch) // 2]

    monkeypatch.setattr(BucketScheduler, "next_batch", next_batch)


def _stale_container(monkeypatch):
    from repro.codec import container

    real, first = container.encode_batch, []

    def encode_batch(*a, **k):
        blob = real(*a, **k)
        first.append(blob)
        return first[0]

    monkeypatch.setattr(container, "encode_batch", encode_batch)


def _altered_read(monkeypatch):
    from repro.serve import ProgressiveServeRoute

    real = ProgressiveServeRoute.full

    def full(self, uid, **k):
        out = np.array(real(self, uid, **k))
        out[3, 4] ^= 1
        return out

    monkeypatch.setattr(ProgressiveServeRoute, "full", full)


def _stale_read(monkeypatch):
    from repro.serve import ProgressiveServeRoute

    real, first = ProgressiveServeRoute.full, []

    def full(self, uid, **k):
        first.append(real(self, uid, **k))
        return first[0]

    monkeypatch.setattr(ProgressiveServeRoute, "full", full)


@pytest.mark.parametrize("cell_name", list(CELLS))
def test_sound_run_is_correct_and_reports_its_metrics(cell_name):
    result = _run(cell_name)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2
    assert list(result)[-1] == "checks"
    assert all(c["value"] <= c["limit"] or name == "checked"
               for name, c in result["checks"].items())


@pytest.mark.parametrize("cell_name", list(CELLS))
def test_control_one_bit_below_the_stored_precision_is_not_correct(cell_name):
    result = _run(cell_name, control="lsb")
    assert result["correct"] is False
    assert result["checks"]["mismatched_samples"]["value"] > 0


@pytest.mark.parametrize("cell_name,fault", [
    ("j2k4k-ingest", _altered_pyramid),
    ("j2k4k-ingest", _half_batch),
    ("j2k4k-ingest", _stale_container),
    ("ct512-ingest-open", _altered_pyramid),
    ("ct512-ingest-open", _half_batch),
    ("ct512-ingest-open", _stale_container),
    ("ct512-read", _altered_read),
    ("ct512-read", _stale_read),
])
def test_each_fault_in_the_timed_path_makes_the_run_not_correct(monkeypatch, cell_name, fault):
    fault(monkeypatch)
    result = _run(cell_name)
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("cell_name,shapes", [("j2k4k-ingest", 4), ("ct512-ingest-open", 1)])
def test_a_sampled_check_covers_every_shape_and_still_fails_the_control(
        monkeypatch, cell_name, shapes):
    monkeypatch.setattr(harness, "CHECK_BUCKET_SAMPLES", 1)  # one container per shape
    sound = _run(cell_name)
    assert sound["correct"] is True, sound["checks"]
    checked = sound["checks"]["checked"]["value"]
    assert shapes <= checked < sound["attempted"]
    control = _run(cell_name, control="lsb")
    assert control["correct"] is False
    assert control["checks"]["mismatched_samples"]["value"] > 0


def test_a_traced_read_traces_only_the_first_part_of_its_window():
    bench = registry.benchmark()
    cell = registry.cell("ct512-read", bench)
    cfg = dict(registry.config(cell["config"]), **CELLS["ct512-read"])
    mix = dict(registry.traffic(cell["traffic"]), trace_seconds=0.3)
    window, result = bench_run.run_cell(
        cell, cfg, mix, bench, seed=SEED, seconds=4.0, traced=True,
        control="none", device=DEVICE, peaks=PEAKS,
    )
    assert result["correct"] is True
    assert 0.3 <= result["device"]["window_s"] < 1.0 < window["window_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
