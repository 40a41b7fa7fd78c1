"""Unit tests for the checked-in CI gate logic (benchmarks/gate.py).

The gate used to live as an untestable heredoc inside smoke.sh; these
fixtures run a known-good payload through every gate (must pass clean)
and then break it one field at a time (each break must produce exactly
the expected failure), so a gate regression is caught in tier-1 instead
of silently green-lighting broken benchmarks.
"""
import copy
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from benchmarks import gate  # noqa: E402


def _good_rows() -> dict:
    rows = {
        "table2.ls.adders": "4.0",
        "table2.ls.shifters": "2.0",
        "table2.ls.multipliers": "0.0",
        "table2.scheme.cdf53.adders": "4.0",
        "table2.scheme.cdf53.shifters": "2.0",
    }
    for name in gate.REQUIRED_SCHEMES:
        rows[f"table2.scheme.{name}.multipliers"] = "0.0"
    return rows


def _good_bench() -> dict:
    scheme_row = {
        "bit_exact": True,
        "multipliers_per_pair": 0,
        "adders_per_pair": 4,
        "shifters_per_pair": 2,
    }
    return {
        "platform": "cpu",
        "default_backend": "xla",
        "bit_exact": True,
        "1d_multilevel": {
            "shape": [8, 16384], "levels": 3,
            "speedup_fused_vs_interpret": 4.0,
        },
        "2d": {"shape": [256, 256], "speedup_fused_vs_interpret": 5.0},
        "2d_large": {
            "shape": [2048, 2048], "plan": "xla", "bit_exact": True,
            "fwd_us": 1.0, "inv_us": 1.0,
        },
        "2d_pyramid": {
            "shape": [2048, 2048], "levels": 3, "bit_exact": True,
            "speedup_fused_vs_per_level": 1.0,
        },
        "2d_batched": {"shape": [16, 256, 256], "levels": 2, "images_per_s": 100.0},
        "schemes": {n: dict(scheme_row) for n in gate.REQUIRED_SCHEMES},
        "3d": {
            "shape": [16, 64, 64], "levels": 2, "plan": "xla",
            "bit_exact": True, "per_axis_us": 8.0, "fused_us": 1.0,
            "speedup_fused_vs_per_axis": 8.0,
            "schemes": {n: {"bit_exact": True, "fwd_us": 1.0}
                        for n in gate.REQUIRED_SCHEMES},
        },
        "3d_large": {"shape": [64, 512, 512], "plan": "xla"},
        "codec": {
            "block": 256,
            "lossless": {n: True for n in gate.REQUIRED_SCHEMES},
            "encode_mbps": 10.0,
            "decode_mbps": 10.0,
            "smooth": {
                "raw_bytes": 196608, "wz_rice_bytes": 20000,
                "zlib_bytes": 60000, "ratio_vs_zlib": 3.0,
            },
            "noisy": {
                "raw_bytes": 196608, "wz_rice_bytes": 90000,
                "zlib_bytes": 180000, "ratio_vs_zlib": 2.0,
            },
        },
        "resilience": {
            "container_bytes": 50000,
            "parity_overhead_bytes": 9000,
            "parity_overhead_ratio": 0.18,
            "single_band_recovery": True,
            "recovery": {
                "bit-flip": "recovered",
                "truncation": "typed-error",
                "save-crash": "previous-intact",
                "pallas-failure": "typed-error",
                "stuck-neighbor": "typed-error",
                "deadline-miss": "typed-error",
            },
        },
        "serve": {
            "buckets": [[16, 16], [32, 32]],
            "batch_slots": 8,
            "levels": 2,
            "requests": 32,
            "requests_per_s": 100.0,
            "p99_ms": 50.0,
            "compiles": 2,
            "cache_hit_rate": 1.0,
            "batch_encode_ms": 1.0,
            "per_request_encode_ms": 4.0,
            "batch_encode_speedup": 4.0,
            "thumbnail_bytes_fraction": 0.1,
        },
        "ranges": {
            "certificates": {
                "cdf53": {"safe_abs_1d_l1": gate.CDF53_SAFE_ABS_1D_L1,
                          "safe_abs_2d_l2": 268435455,
                          "growth_bits_1d_l1": 1.0,
                          "int16_levels_3d": 5},
                "haar": {"safe_abs_1d_l1": 1073741823,
                         "safe_abs_2d_l2": 536870911,
                         "growth_bits_1d_l1": 1.0,
                         "int16_levels_3d": 5},
                "cdf22": {"safe_abs_1d_l1": 536870911,
                          "safe_abs_2d_l2": 134217727,
                          "growth_bits_1d_l1": 2.0,
                          "int16_levels_3d": 4},
                "97m": {"safe_abs_1d_l1": 12005499,
                        "safe_abs_2d_l2": 928521,
                        "growth_bits_1d_l1": 7.5,
                        "int16_levels_3d": 1},
            },
            "wraparound": {e: "typed-error" for e in gate.CHECKED_ENGINES},
            "roundtrip_exact": True,
            "overhead_off_x": 1.01,
            "overhead_on_x": 4.0,
        },
        "observability": {
            "overhead_x": 1.01,
            "events": {k: 2 for k in gate.OBS_EVENT_KINDS},
            "event_total": 12,
            "metric_subsystems": list(gate.OBS_SUBSYSTEMS),
            "span_subsystems": list(gate.OBS_SUBSYSTEMS),
        },
    }


def test_parse_rows_skips_header_and_malformed():
    rows = gate.parse_rows(
        "name,value,notes\nfoo.bar,3.0,a note, with commas\njunk\n"
    )
    assert rows == {"foo.bar": "3.0"}


def test_good_fixture_passes_every_gate():
    assert gate.gate_failures(_good_rows(), _good_bench()) == []


def test_summary_mentions_3d():
    s = gate.summary(_good_bench())
    assert "3d fused/per-axis" in s and s.startswith("SMOKE OK")


def test_table2_regression_fails():
    rows = _good_rows()
    rows["table2.ls.multipliers"] = "1.0"
    fails = gate.gate_failures(rows, _good_bench())
    assert any("table2.ls.multipliers" in f for f in fails)


def test_scheme_multiplies_fail():
    rows = _good_rows()
    rows["table2.scheme.97m.multipliers"] = "2.0"
    fails = gate.check_table2(rows)
    assert any("97m" in f and "multiplierless" in f for f in fails)


def test_missing_section_fails_schema_before_behaviour():
    bench = _good_bench()
    del bench["3d"]
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("missing section '3d'" in f for f in fails)


def test_row_level_schema_failure_stops_before_behavioural_gates():
    """ANY schema failure must short-circuit gate_failures: the
    behavioural gates index the payload freely and would KeyError on a
    half-broken row instead of reporting the promised failure list."""
    bench = _good_bench()
    del bench["schemes"]["cdf53"]["bit_exact"]
    fails = gate.gate_failures(_good_rows(), bench)  # must not raise
    assert any("schemes['cdf53'] missing 'bit_exact'" in f for f in fails)


def test_missing_multipliers_field_fails_schema():
    """The bench-side multiplierless check reads multipliers_per_pair;
    an emission that drops the field must fail the schema gate (not
    silently pass the behavioural one)."""
    bench = _good_bench()
    del bench["schemes"]["97m"]["multipliers_per_pair"]
    fails = gate.gate_failures(_good_rows(), bench)
    assert any(
        "schemes['97m'] missing 'multipliers_per_pair'" in f for f in fails
    )


def test_missing_3d_scheme_row_fails():
    bench = _good_bench()
    del bench["3d"]["schemes"]["haar"]
    fails = gate.check_schema(bench)
    assert any("3d.schemes" in f and "haar" in f for f in fails)


def test_3d_bit_exact_break_fails():
    bench = _good_bench()
    bench["3d"]["bit_exact"] = False
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("3d: fused volume transform diverged" in f for f in fails)


def test_3d_scheme_roundtrip_break_fails():
    bench = _good_bench()
    bench["3d"]["schemes"]["cdf22"]["bit_exact"] = False
    fails = gate.check_3d(bench)
    assert fails == ["3d scheme cdf22: volume round-trip diverged"]


def test_3d_speedup_regression_fails():
    bench = _good_bench()
    bench["3d"]["speedup_fused_vs_per_axis"] = 0.3
    fails = gate.check_3d(bench)
    assert any("regressed vs per-axis" in f for f in fails)


def test_accelerator_plan_gates():
    """On a pallas-default platform, large 2D/3D shapes must stay on the
    tiled/slab Pallas paths."""
    bench = _good_bench()
    bench["default_backend"] = "pallas"
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("2d_large" in f and "left the Pallas path" in f for f in fails)
    assert any("3d_large" in f and "left the Pallas path" in f for f in fails)
    bench["2d_large"]["plan"] = "tiled-pallas"
    bench["3d_large"]["plan"] = "slab-pallas"
    assert gate.gate_failures(_good_rows(), bench) == []


def test_interpret_speedup_floor():
    bench = _good_bench()
    bench["2d"]["speedup_fused_vs_interpret"] = 0.9
    fails = gate.check_kernels(bench)
    assert any("2d: fused compiled path no faster" in f for f in fails)


def test_codec_lossless_break_fails():
    bench = _good_bench()
    bench["codec"]["lossless"]["97m"] = False
    fails = gate.check_codec(bench)
    assert fails == ["codec scheme 97m: container roundtrip diverged"]


def test_codec_ratio_regression_fails():
    """wz-rice losing to plain zlib on the smooth checkpoint-like tensor
    is the acceptance regression the codec gate exists to catch."""
    bench = _good_bench()
    bench["codec"]["smooth"]["wz_rice_bytes"] = 70000
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("codec smooth" in f and "lost to plain zlib" in f for f in fails)


def test_codec_missing_scheme_row_fails_schema():
    bench = _good_bench()
    del bench["codec"]["lossless"]["cdf22"]
    fails = gate.check_schema(bench)
    assert any("codec.lossless" in f and "cdf22" in f for f in fails)


def test_codec_missing_ratio_key_fails_schema():
    bench = _good_bench()
    del bench["codec"]["noisy"]["zlib_bytes"]
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("codec.noisy missing key 'zlib_bytes'" in f for f in fails)


def test_summary_mentions_codec():
    assert "codec lossless" in gate.summary(_good_bench())


def test_resilience_silent_corruption_fails():
    """A bit-flip that decodes without healing is silent corruption —
    the one outcome the resilience layer exists to rule out."""
    bench = _good_bench()
    bench["resilience"]["recovery"]["bit-flip"] = "silent"
    fails = gate.check_resilience(bench)
    assert any("bit-flip" in f and "'silent'" in f for f in fails)


def test_resilience_heal_break_fails():
    bench = _good_bench()
    bench["resilience"]["single_band_recovery"] = False
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("did NOT heal" in f for f in fails)


def test_resilience_parity_ratio_bounds():
    """Parity must cost something (>0: the group really exists) but
    never approach a full duplicate (<1)."""
    for bad in (0, 1.0, 2.5, -0.1, True):
        bench = _good_bench()
        bench["resilience"]["parity_overhead_ratio"] = bad
        fails = gate.check_resilience(bench)
        assert any("parity_overhead_ratio" in f for f in fails), bad


def test_resilience_missing_fault_class_fails():
    bench = _good_bench()
    del bench["resilience"]["recovery"]["stuck-neighbor"]
    fails = gate.check_resilience(bench)
    assert any("stuck-neighbor" in f and "missing" in f for f in fails)


def test_resilience_unknown_fault_class_fails():
    """Taxonomy and gate move together: a new fault class emitted by the
    bench without a pinned expectation here must fail loudly."""
    bench = _good_bench()
    bench["resilience"]["recovery"]["cosmic-ray"] = "recovered"
    fails = gate.check_resilience(bench)
    assert any("cosmic-ray" in f and "unknown fault class" in f for f in fails)


def test_resilience_missing_section_fails_schema():
    bench = _good_bench()
    del bench["resilience"]
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("missing section 'resilience'" in f for f in fails)


def test_summary_mentions_resilience():
    s = gate.summary(_good_bench())
    assert "resilience parity=0.18" in s and "band-heal=True" in s


def test_ranges_silent_wraparound_fails():
    """A checked engine that lets a wrapping input through silently is
    the exact corruption mode the certificates exist to rule out."""
    bench = _good_bench()
    bench["ranges"]["wraparound"]["fused-3d"] = "silent"
    fails = gate.check_ranges(bench)
    assert any("fused-3d" in f and "silently" in f for f in fails)


def test_ranges_missing_engine_fails():
    bench = _good_bench()
    del bench["ranges"]["wraparound"]["sharded-2d"]
    fails = gate.check_ranges(bench)
    assert any("sharded-2d" in f and "missing" in f for f in fails)


def test_ranges_unknown_engine_fails():
    bench = _good_bench()
    bench["ranges"]["wraparound"]["warp-engine"] = "typed-error"
    fails = gate.check_ranges(bench)
    assert any("warp-engine" in f and "unknown engine" in f for f in fails)


def test_ranges_certificate_pin():
    """A drifted cdf53 certificate means the tracer's semantics moved."""
    bench = _good_bench()
    bench["ranges"]["certificates"]["cdf53"]["safe_abs_1d_l1"] += 1
    fails = gate.check_ranges(bench)
    assert any("pinned" in f for f in fails)


def test_ranges_monotonicity_and_missing_scheme():
    bench = _good_bench()
    bench["ranges"]["certificates"]["haar"]["safe_abs_2d_l2"] = 0
    fails = gate.check_ranges(bench)
    assert any("haar" in f and "positive-monotone" in f for f in fails)
    bench2 = _good_bench()
    del bench2["ranges"]["certificates"]["97m"]
    fails2 = gate.check_ranges(bench2)
    assert any("97m" in f for f in fails2)


def test_ranges_checked_off_must_be_free():
    bench = _good_bench()
    bench["ranges"]["overhead_off_x"] = 5.2
    fails = gate.check_ranges(bench)
    assert any("not free" in f for f in fails)


def test_ranges_roundtrip_break_fails():
    bench = _good_bench()
    bench["ranges"]["roundtrip_exact"] = False
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("round-trip bit-exactly under checked" in f for f in fails)


def test_ranges_missing_section_fails_schema():
    bench = _good_bench()
    del bench["ranges"]
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("missing section 'ranges'" in f for f in fails)


def test_summary_mentions_ranges():
    s = gate.summary(_good_bench())
    assert "ranges checked=6 engines typed" in s


def test_serve_cache_miss_after_warmup_fails():
    """A hit rate below 1.0 means something recompiled under the warmed
    mixed-bucket workload — the exact regression the executable cache
    exists to rule out."""
    bench = _good_bench()
    bench["serve"]["cache_hit_rate"] = 0.75
    fails = gate.check_serve(bench)
    assert any("hit rate 0.75" in f and "recompiled" in f for f in fails)


def test_serve_recompile_per_request_fails():
    bench = _good_bench()
    bench["serve"]["compiles"] = 7
    fails = gate.check_serve(bench)
    assert any("7 compiles for 2 buckets" in f for f in fails)


def test_serve_batch_encode_speedup_floor():
    bench = _good_bench()
    bench["serve"]["batch_encode_speedup"] = 1.2
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("below the 1.5x floor" in f for f in fails)


def test_serve_thumbnail_fraction_bounds():
    """The thumbnail tier must read a STRICT byte subset: a fraction of
    1.0 means progressive decode degenerated into a full read, 0 or
    negative means the accounting broke."""
    for bad in (0, 1.0, 1.7, -0.2, True):
        bench = _good_bench()
        bench["serve"]["thumbnail_bytes_fraction"] = bad
        fails = gate.check_serve(bench)
        assert any("thumbnail tier" in f for f in fails), bad


def test_serve_nonpositive_throughput_fails():
    bench = _good_bench()
    bench["serve"]["requests_per_s"] = 0
    fails = gate.check_serve(bench)
    assert any("non-positive throughput" in f for f in fails)


def test_serve_missing_section_fails_schema():
    bench = _good_bench()
    del bench["serve"]
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("missing section 'serve'" in f for f in fails)


def test_summary_mentions_serve():
    s = gate.summary(_good_bench())
    assert "serve 100.0 req/s" in s and "hit-rate=1.0" in s


def test_obs_overhead_over_budget_fails():
    """Instrumentation costing more than the gate budget on the serve
    workload means it is no longer cheap enough to leave on."""
    bench = _good_bench()
    bench["observability"]["overhead_x"] = 1.25
    fails = gate.check_obs(bench)
    assert any("too expensive to leave on" in f for f in fails)


def test_obs_subsystem_going_dark_fails():
    bench = _good_bench()
    bench["observability"]["metric_subsystems"].remove("codec")
    fails = gate.gate_failures(_good_rows(), bench)
    assert any(
        "metric_subsystems" in f and "codec" in f for f in fails
    )


def test_obs_span_coverage_checked_separately():
    bench = _good_bench()
    bench["observability"]["span_subsystems"] = ["serve"]
    fails = gate.check_obs(bench)
    assert any("span_subsystems" in f for f in fails)
    assert not any("metric_subsystems" in f for f in fails)


def test_obs_silent_event_site_fails():
    """A chaos run that produces zero events of a kind means that event
    site stopped emitting — the instrumentation analogue of silent
    corruption."""
    bench = _good_bench()
    bench["observability"]["events"]["RetryEvent"] = 0
    fails = gate.check_obs(bench)
    assert any("no RetryEvent" in f for f in fails)
    bench["observability"]["events"].pop("HealEvent")
    fails = gate.check_obs(bench)
    assert any("no HealEvent" in f for f in fails)


def test_obs_event_total_below_ring_count_fails():
    bench = _good_bench()
    bench["observability"]["event_total"] = 3
    fails = gate.check_obs(bench)
    assert any("unbounded total regressed" in f for f in fails)


def test_obs_missing_section_fails_schema():
    bench = _good_bench()
    del bench["observability"]
    fails = gate.gate_failures(_good_rows(), bench)
    assert any("missing section 'observability'" in f for f in fails)


def test_summary_mentions_obs():
    s = gate.summary(_good_bench())
    assert "obs overhead=1.01x" in s and "subsystems=5" in s


def test_main_exit_codes(tmp_path):
    csv = tmp_path / "rows.csv"
    csv.write_text(
        "name,value,notes\n"
        + "\n".join(f"{k},{v},x" for k, v in _good_rows().items())
        + "\n"
    )
    bench_path = tmp_path / "bench.json"
    bench_path.write_text(json.dumps(_good_bench()))
    assert gate.main(["--csv", str(csv), "--bench", str(bench_path)]) == 0
    broken = _good_bench()
    broken["bit_exact"] = False
    bench_path.write_text(json.dumps(broken))
    assert gate.main(["--csv", str(csv), "--bench", str(bench_path)]) == 1


def test_fixture_stays_schema_complete():
    """The passing fixture must keep covering every required section/key
    (otherwise the failing-fixture tests could rot into vacuity)."""
    bench = _good_bench()
    assert gate.check_schema(bench) == []
    mutated = copy.deepcopy(bench)
    mutated["3d"].pop("speedup_fused_vs_per_axis")
    assert gate.check_schema(mutated) != []
