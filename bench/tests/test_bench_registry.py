"""Everything the harness runs is found by name, from files alone."""
import json
import re
import shutil

import pytest

from bench import registry
from bench import run as bench_run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return registry.benchmark()


def test_every_cell_resolves_to_its_files(bench):
    for cell in bench["workloads"]:
        cfg = registry.config(cell["config"])
        assert cfg["name"] == cell["config"]
        assert callable(registry.system(cfg["system"]).build)
        assert callable(registry.sample(cfg["samples"]["kind"]).make)
        pattern = registry.pattern(registry.traffic(cell["traffic"])["pattern"])
        assert callable(pattern.setup) and callable(pattern.window)
        for trace in (False, True):
            for m in registry.metrics_for(cell["name"], bench, trace):
                assert callable(registry.metric(m["name"]))
    for entry in bench["configs"]:
        assert (registry.ROOT.parent / entry["file"]).is_file()


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        names = {m["name"] for m in registry.metrics_for(cell["name"], bench, False)}
        assert "setup_s" in names and len(names) >= 2
        layer = registry.metrics_for(cell["name"], bench, True)
        assert layer
        for m in layer:  # the e2e metric a layer metric moves is reported there
            assert m["moves"] in names and m["moves"] in e2e


def test_names_units_and_bounds_keep_to_the_contract(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    for item in metrics + bench["workloads"] + bench["configs"]:
        assert NAME.match(item["name"]), item["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert len(json.dumps(bench)) < 64 * 1024


def test_a_new_config_mix_and_metric_are_found_as_new_files(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "mr-256.json").write_text('{"name": "mr-256", "levels": 4}')
    (tmp_path / "traffic" / "slow.json").write_text('{"pattern": "open", "rate_per_s": 3}')
    (tmp_path / "metrics" / "answers.x.py").write_text(
        "def read(run):\n    return float(len(run))\n"
    )
    (tmp_path / "peaks.json").write_text(json.dumps(
        {"devices": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}}
    ))
    assert registry.config("mr-256", tmp_path)["levels"] == 4
    assert registry.traffic("slow", tmp_path)["rate_per_s"] == 3
    assert registry.metric("answers.x", tmp_path)([1, 2, 3]) == 3.0
    assert registry.peaks("TPU v5 lite", tmp_path)["hbm_bytes_per_s"] == 819e9


def test_a_device_kind_missing_from_the_peaks_table_is_an_error():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        registry.peaks("TPU v9 imaginary")


def test_a_missing_file_is_an_error():
    with pytest.raises(FileNotFoundError):
        registry.config("no-such-config")
    with pytest.raises(FileNotFoundError):
        registry.metric("no_such_metric")


def test_a_split_metric_shares_the_reader_of_its_stem(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "idle.py").write_text("def read(run):\n    return 1.0\n")
    (tmp_path / "metrics" / "idle.read.py").write_text("def read(run):\n    return 2.0\n")
    assert registry.metric("idle.open", tmp_path)(None) == 1.0
    assert registry.metric("idle.read", tmp_path)(None) == 2.0
    assert registry.metric("device_idle.bulk") is not registry.metric("codec_roofline.read")


PATTERN = '''"""Every pool image once, as one engine.run call."""
from bench import harness
from bench.harness import CLOCK, Record


def setup(run, engine, pool):
    engine.warmup()
    return engine, pool


def window(run, state):
    from repro.serve import TransformRequest

    engine, pool = state
    records = {i: Record(i, i, img.shape, due=0.0) for i, img in enumerate(pool)}
    t0 = CLOCK()
    done = engine.run([TransformRequest(uid=i, image=img) for i, img in enumerate(pool)])
    run.window_s = harness.finish(done, records, t0, t0) - t0
    run.records = list(records.values())
'''

SAMPLES = '''"""A ramp with seeded noise."""
import numpy as np


def make(shape, rng, config):
    ramp = np.add.outer(np.arange(shape[0]), np.arange(shape[1])) * config["samples"]["step"]
    return (ramp + rng.integers(-3, 4, shape)).astype(np.int16)
'''


def test_a_new_pattern_and_sample_model_run_as_new_files(tmp_path):
    """A pattern and a sample model planted as new files run through
    ``run_cell`` beside the existing system and readers, with no edit."""
    for kind in ("systems", "metrics"):
        shutil.copytree(registry.ROOT / kind, tmp_path / kind)
    (tmp_path / "patterns").mkdir()
    (tmp_path / "samples").mkdir()
    (tmp_path / "patterns" / "oneshot.py").write_text(PATTERN)
    (tmp_path / "samples" / "ramp.py").write_text(SAMPLES)
    cfg = dict(registry.config("dicom-ct-512"), buckets=[[32, 32]], levels=2,
               request_shapes=[[32, 32], [24, 30]], pool_size=6,
               samples={"kind": "ramp", "step": 5})
    cell = {"name": "ramp-oneshot", "config": "ramp", "traffic": "oneshot", "chips": 1}
    bench = {
        "workloads": [cell],
        "end_to_end": [{"name": n, "unit": "x"} for n in
                       ("setup_s", "ingest_msamples_per_s", "coded_bits_per_sample")],
        "per_layer": [],
    }
    _, result = bench_run.run_cell(
        cell, cfg, {"pattern": "oneshot"}, bench, seed=2**31 + 5, seconds=1.0,
        traced=False, control="none", device={"platform": "cpu"}, peaks={}, root=tmp_path,
    )
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] == 6 and result["checks"]["checked"]["value"] == 6
    assert set(result["metrics"]) == {"setup_s", "ingest_msamples_per_s", "coded_bits_per_sample"}
