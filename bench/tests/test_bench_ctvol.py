"""The CT volume archive (``dicom-ct-volume``, cell ``ctvol-ingest``): its
configuration and metrics, its sample model, the reader of the volume
transform's roofline, and whole runs of a shrunken copy on the CPU."""
import numpy as np
import pytest

from bench import data, registry
from bench import run as bench_run
from bench.tests.test_bench_faults import DEVICE, PEAKS, SEED

CELL = "ctvol-ingest"
CONFIG = "dicom-ct-volume"
TINY = dict(buckets=[[16, 64, 64]], request_shapes=[[16, 64, 64]], levels=3)


def test_the_configuration_names_its_source_and_cuts_nothing():
    bench = registry.benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    cfg = registry.config(CONFIG)
    assert entry["reduced"] == [] and cfg["reduced"] == []
    assert "1.2.840.10008.1.2.4.92" in entry["source"] and "JP3D" in entry["source"]
    assert cfg["buckets"] == [[256, 512, 512]] == cfg["request_shapes"]
    assert (cfg["scheme"], cfg["mode"], cfg["levels"]) == ("cdf53", "jpeg2000", 5)
    assert (cfg["batch_slots"], cfg["pool_size"], cfg["bits_stored"]) == (1, 2, 12)
    assert "200-600" in cfg["published"]["series_slices"]
    assert "Part 2" in cfg["assumed"]["transform"] and "JP3D" in cfg["assumed"]["transform"]


def test_the_cell_reports_exactly_its_metrics():
    bench = registry.benchmark()
    cell = registry.cell(CELL, bench)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "backlog", 1)
    e2e = {m["name"] for m in registry.metrics_for(CELL, bench, False)}
    layer = {m["name"] for m in registry.metrics_for(CELL, bench, True)}
    assert e2e == {"setup_s", "ingest_msamples_per_s", "coded_bits_per_sample"}
    assert layer == {"device_idle.vol", "codec_roofline.vol", "host_ms_per_step.vol",
                     "coder_chunks_per_request.vol", "lift3d_roofline.vol"}
    assert all(callable(registry.metric(name)) for name in layer)


def test_ct_volume_is_fixed_by_the_seed_and_continuous_in_z():
    cfg = registry.config(CONFIG)
    model = registry.sample("ct_volume")
    shape = (64, 128, 128)
    a = model.make(shape, data.rng_for(SEED, 1, 0), cfg)
    assert a.shape == shape and a.dtype == np.int16
    assert np.array_equal(a, model.make(shape, data.rng_for(SEED, 1, 0), cfg))
    b = model.make(shape, data.rng_for(SEED, 1, 1), cfg)
    hu = a.astype(np.int32)
    assert -2048 <= hu.min() and hu.max() <= 2047
    assert np.mean(hu < -900) > 0.2  # air around the body
    assert np.mean(np.abs(hu - 40) < 50) > 0.1  # soft tissue
    assert np.mean((hu > -900) & (hu < -700)) > 0.02  # lungs
    assert np.mean(hu > 500) > 0.001  # vertebrae and ribs
    adjacent = np.mean(np.abs(np.diff(hu, axis=0)))
    between = np.mean(np.abs(hu - b))
    assert adjacent < between / 2, (adjacent, between)


def _roots(monkeypatch, buckets):
    """A tracer whose ring holds one ``serve.step`` root per bucket."""
    from repro import obs
    from repro.obs import Tracer

    tracer = Tracer(64)
    monkeypatch.setattr(obs, "tracer", tracer)
    for b in buckets:
        with tracer.span("serve.step", subsystem="serve", bucket=b):
            with tracer.span("serve.transform", subsystem="serve", bucket=b):
                pass


def _fake_run(ops, batches):
    from bench import harness

    run = harness.Run(cell=CELL, config=dict(registry.config(CONFIG), **TINY), mix={},
                      seed=SEED, seconds=0.0, peaks=PEAKS)
    run.trace = {"busy_s": 1.0, "window_s": 2.0, "device_ops": ops, "idle_gaps": []}
    run.obs_delta = {"serve.batches": float(batches)}
    return run


def test_lift3d_roofline_reads_a_fixed_trace_and_span_window(monkeypatch):
    # an older root outside the window, then the window's two
    _roots(monkeypatch, ["32x32", "16x64x64", "16x64x64"])
    reader = registry.metric("lift3d_roofline.vol")
    ops = [["jit__encode_chunk", 0.5], ["jit_transform_3d", 1e-3]]
    # one 16x64x64 volume at 3 levels: 65536 samples in at 2 B; bands out
    # at 4 B of 65536, 8192 and 1024 samples; inputs of levels 1 and 2 in
    # at 4 B
    per_volume = 2 * 65536 + 4 * (65536 + 8192 + 1024) + 4 * (8192 + 1024)
    want = 100.0 * 2 * per_volume / PEAKS["hbm_bytes_per_s"] / 1e-3
    assert reader(_fake_run(ops, 2)) == pytest.approx(want, rel=1e-12)
    # the 2-D program's name, no program, or a window the ring lacks: nothing
    assert reader(_fake_run([["jit_transform", 1e-3]], 2)) is None
    assert reader(_fake_run([], 2)) is None
    assert reader(_fake_run(ops, 4)) is None


@pytest.mark.parametrize("control", ["none", "lsb"])
def test_a_shrunken_volume_cell_runs_and_is_checked_on_the_cpu(control):
    """The cell's own files with a [16, 64, 64] bucket: a sound run is
    correct and its span readers read; the control is not correct."""
    bench = registry.benchmark()
    cell = registry.cell(CELL, bench)
    cfg = dict(registry.config(cell["config"]), **TINY)
    _, result = bench_run.run_cell(
        cell, cfg, registry.traffic(cell["traffic"]), bench, seed=SEED, seconds=0.6,
        traced=control == "none", control=control, device=DEVICE, peaks=PEAKS,
    )
    if control == "lsb":
        assert result["correct"] is False
        assert result["checks"]["mismatched_samples"]["value"] > 0
        return
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["checked"]["value"] >= 1
    metrics = result["metrics"]
    assert metrics["host_ms_per_step.vol"]["value"] > 0
    # 7 bands a level and the approximation, one container of one volume:
    # (8x32x32 = 8192 samples = 32 blocks) and every smaller band fit a chunk
    assert metrics["coder_chunks_per_request.vol"]["value"] == 1 + 7 * 3
    # no device plane in a CPU trace: the device readers give nothing
    assert "lift3d_roofline.vol" not in metrics and "device_idle.vol" not in metrics
