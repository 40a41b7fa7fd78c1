"""A whole run on the CPU at a tiny size, looking for no chip: sound runs
come out correct; the control and each planted fault come out not correct."""
import shutil

import numpy as np
import pytest

from bench import data, harness, registry
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


# ---------------------------------------------------------------------------
# A volume cell that BENCHMARK.json does not hold: 3-D buckets answered with
# kind-3 batch containers, held to the reference like the 2-D cells.
# ---------------------------------------------------------------------------

STACKED_CT = '''"""Test-only volumes: CT phantom slices stacked along depth, each drawn
from the volume's generator in turn."""
import numpy as np

from bench import registry

_slice = registry.sample("ct_phantom")


def make(shape, rng, config):
    depth, h, w = shape
    return np.stack([_slice.make((h, w), rng, config) for _ in range(depth)])
'''

VOLUME_CELL = {"name": "ct-volume-test", "config": "ct-volume-test", "traffic": "backlog",
               "chips": 1}
VOLUME_CONFIG = dict(
    system="wavelet_serve", scheme="cdf53", mode="jpeg2000", levels=2,
    buckets=[[8, 32, 32]], batch_slots=2, request_shapes=[[8, 32, 32], [7, 32, 27]],
    bits_stored=12, signed=True, samples={"kind": "stacked_ct", "noise_hu": 12.0},
    pool_size=4,
)


@pytest.fixture
def volume_root(tmp_path):
    """A benchmark root holding the shipped patterns, systems and readers,
    and the test's own sample model."""
    root = tmp_path / "bench"
    for kind in ("patterns", "systems", "metrics"):
        shutil.copytree(registry.ROOT / kind, root / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "samples").mkdir()
    (root / "samples" / "stacked_ct.py").write_text(STACKED_CT)
    return root


def _run_volume(root, control="none"):
    shipped = {m["name"]: m for m in registry.benchmark()["end_to_end"]}
    bench = {
        "workloads": [VOLUME_CELL],
        "end_to_end": [dict(shipped[name], workloads=[VOLUME_CELL["name"]]) for name in
                       ("setup_s", "ingest_msamples_per_s", "coded_bits_per_sample")],
        "per_layer": [],
    }
    _, result = bench_run.run_cell(
        VOLUME_CELL, VOLUME_CONFIG, registry.traffic("backlog"), bench, seed=SEED,
        seconds=0.6, traced=False, control=control, device=DEVICE, peaks=PEAKS, root=root,
    )
    return result


def _altered_volume(monkeypatch):
    from repro.serve.executor import TransformExecutor

    real = TransformExecutor.run

    def run(self, fn, batch, key):
        out = real(self, fn, batch, key)
        return out._replace(approx=out.approx.at[0, 0, 0, 0].add(1))

    monkeypatch.setattr(TransformExecutor, "run", run)


def test_a_volume_cell_is_checked_against_the_reference(volume_root):
    sound = _run_volume(volume_root)
    assert sound["correct"] is True, sound["checks"]
    assert sound["checks"]["checked"]["value"] >= 1
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert {"setup_s", "ingest_msamples_per_s", "coded_bits_per_sample"} <= set(sound["metrics"])
    control = _run_volume(volume_root, control="lsb")
    assert control["correct"] is False
    assert control["checks"]["mismatched_samples"]["value"] > 0


@pytest.mark.parametrize("fault", [_altered_volume, _half_batch, _stale_container])
def test_each_fault_in_a_volume_cell_makes_the_run_not_correct(monkeypatch, volume_root, fault):
    fault(monkeypatch)
    result = _run_volume(volume_root)
    assert result["correct"] is False, result["checks"]


# ---------------------------------------------------------------------------
# The 2-D cells check the containers they checked when buckets were sized
# as ``h * w``.
# ---------------------------------------------------------------------------


def _containers_to_check_by_h_times_w(run):
    """``harness.containers_to_check`` as it was before N-D buckets."""
    rows = {}
    for rec in run.records:
        if rec.answered and rec.blob is not None:
            rows.setdefault(id(rec.blob), set()).add(rec.shape)
    blobs = list(rows)
    order = [blobs[i] for i in data.rng_for(run.seed, 5).permutation(len(blobs))]
    bucket = max(h * w for h, w in run.config["buckets"]) * run.config["batch_slots"]
    picked, shapes = set(), set()
    for b in order:
        if rows[b] - shapes:
            picked.add(b)
            shapes |= rows[b]
    for b in order:
        if len(picked) * bucket >= harness.CHECK_BUCKET_SAMPLES:
            break
        picked.add(b)
    return picked


@pytest.mark.parametrize("budget_buckets", [None, 0, 5, 100])
@pytest.mark.parametrize("sizes", ["tiny", "shipped"])
@pytest.mark.parametrize("cell_name", list(CELLS))
def test_two_d_cells_check_the_same_containers_as_before(monkeypatch, cell_name, sizes,
                                                         budget_buckets):
    cfg = registry.config(registry.cell(cell_name, registry.benchmark())["config"])
    if sizes == "tiny":
        cfg = dict(cfg, **CELLS[cell_name])
    (h, w), = cfg["buckets"]
    if budget_buckets is not None:  # None: the shipped budget
        monkeypatch.setattr(harness, "CHECK_BUCKET_SAMPLES",
                            max(1, budget_buckets * h * w * cfg["batch_slots"]))
    rng = np.random.default_rng(budget_buckets or 1)
    shapes = [tuple(s) for s in cfg["request_shapes"]]
    records, uid = [], 0
    for c in range(40):
        blob = b"container" + c.to_bytes(4, "little")
        for row in range(int(rng.integers(1, cfg["batch_slots"] + 1))):
            shape = shapes[int(rng.integers(len(shapes)))]
            records.append(harness.Record(uid, 0, shape, due=0.0, finished=1.0, blob=blob,
                                          batch_index=row))
            uid += 1
    for seed in (SEED, 2**31 + 4099, 3):
        run = harness.Run(cell=cell_name, config=cfg, mix={}, seed=seed, seconds=0.0,
                          records=records)
        picked = harness.containers_to_check(run)
        assert picked == _containers_to_check_by_h_times_w(run)
        assert len(picked) >= 1
