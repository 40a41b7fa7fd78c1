"""CI regression gates for the benchmark outputs — checked in, unit-tested.

``benchmarks/smoke.sh`` used to carry this logic as an inline heredoc,
which nothing unit-tested; this module is the single home for every
gate, importable by the tier-1 suite (``tests/test_gate.py`` runs
passing AND failing fixtures through it) and runnable as the smoke
step::

    python -m benchmarks.gate --csv <rows.csv> --bench BENCH_kernels.json

Gates (fail = non-zero exit, every failure listed):

  * Table 2 — the paper's op counts hold exactly, and every registered
    scheme's traced ledger shows ZERO multiplies (the multiplierless
    claim; schemes are discovered from the emitted rows so a newly
    registered scheme is gated automatically).
  * Schema — ``BENCH_kernels.json`` carries every required section and
    key (including the ``3d`` section and its per-scheme rows), so a
    broken emission fails fast instead of KeyError-ing mid-gate.
  * Kernel engine — bit-exactness vs the oracle on every fused path
    (1D/2D/2D-large/pyramid/per-scheme/3D), the fused compiled paths
    beating the per-level interpret baseline, the fused pyramid and the
    fused 3D engine not regressing vs per-level / per-axis dispatch,
    and budget-sized 2D images / video-scale 3D volumes never silently
    leaving the Pallas path where Pallas is the platform default.
  * Entropy codec — every registered scheme's 1D/2D/3D pyramids
    round-trip bit-exactly through the WZRC Rice container, and the
    ``wz-rice`` checkpoint codec beats plain zlib bytes on both the
    smooth checkpoint-like tensor and the fp32-noise one.
  * Resilience — the XOR parity group costs a real fraction of the
    container (one band, not free, not a doubling), a single damaged
    band heals bit-exactly, and every fault class in the injection
    taxonomy lands on its expected outcome (recover / degrade /
    typed-error / previous-intact — never silent).
  * Range certificates — the derived int32 safety bounds keep their
    meaning (cdf53 pinned exactly, all positive-monotone), the checked
    arithmetic mode turns a wrap-capable input into a typed error on
    EVERY engine, certified inputs round-trip bit-exactly under
    checking, and the disabled path costs nothing.
  * Serve tier — the compiled-executable cache takes a mixed-bucket
    workload with a 100% hit rate after warmup (nothing recompiles on
    admission or bucket switch), the batch-level response encode beats
    the per-request loop by 1.5x+, and the progressive thumbnail tier
    reads a strict fraction of the stored container's bytes.
  * Observability — the ``repro.obs`` instrumentation costs <= 1.10x on
    the serve throughput workload vs the disabled arm, every subsystem
    reports live metric series AND spans after one seeded chaos run,
    and that run emits every event kind in the taxonomy at least once.

This module is dependency-free (stdlib only) on purpose: the gates must
stay runnable — and unit-testable — without importing jax.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

# every scheme the registry ships; a bench emission missing one of these
# rows (or a registry regression dropping a scheme) fails the gate
REQUIRED_SCHEMES = ("cdf53", "haar", "97m", "cdf22")

# required BENCH_kernels.json structure: section -> keys that must exist
REQUIRED_SECTIONS: Dict[str, tuple] = {
    "1d_multilevel": ("shape", "levels", "speedup_fused_vs_interpret"),
    "2d": ("shape", "speedup_fused_vs_interpret"),
    "2d_large": ("shape", "plan", "bit_exact", "fwd_us", "inv_us"),
    "2d_pyramid": ("shape", "levels", "bit_exact", "speedup_fused_vs_per_level"),
    "2d_batched": ("shape", "levels", "images_per_s"),
    "schemes": (),
    "3d": (
        "shape",
        "levels",
        "plan",
        "bit_exact",
        "per_axis_us",
        "fused_us",
        "speedup_fused_vs_per_axis",
        "schemes",
    ),
    "3d_large": ("shape", "plan"),
    "codec": (
        "block",
        "lossless",
        "encode_mbps",
        "decode_mbps",
        "smooth",
        "noisy",
    ),
    "resilience": (
        "parity_overhead_bytes",
        "parity_overhead_ratio",
        "single_band_recovery",
        "recovery",
    ),
    "ranges": (
        "certificates",
        "wraparound",
        "roundtrip_exact",
        "overhead_off_x",
        "overhead_on_x",
    ),
    "serve": (
        "buckets",
        "batch_slots",
        "requests_per_s",
        "p99_ms",
        "compiles",
        "cache_hit_rate",
        "batch_encode_speedup",
        "thumbnail_bytes_fraction",
    ),
    "observability": (
        "overhead_x",
        "events",
        "event_total",
        "metric_subsystems",
        "span_subsystems",
    ),
}

# batch-level response encode (one WZRC container per micro-batch) must
# amortize the per-request coder overhead by at least this much on the
# bench workload — the reason PR 8 moved the encode to the batch level
MIN_BATCH_ENCODE_SPEEDUP = 1.5

# instrumentation must be cheap enough to leave on in production: the
# serve throughput workload with the obs layer live may cost at most
# this much over the obs.disabled() bare arm (drift-cancelled pairs)
MAX_OBS_OVERHEAD = 1.10

# every subsystem the obs layer must cover (metric series AND spans),
# and every event kind one seeded chaos run must produce at least once
OBS_SUBSYSTEMS = ("ckpt", "codec", "collectives", "kernels", "serve")
OBS_EVENT_KINDS = (
    "AdmissionEvent",
    "DegradeEvent",
    "DispatchEvent",
    "FaultEvent",
    "HealEvent",
    "RetryEvent",
)

# every engine the checked mode must cover; a wrap-capable input through
# any of them must surface as IntegerOverflowError ("typed-error"), never
# as silently wrong bands ("silent")
CHECKED_ENGINES = (
    "oracle-1d",
    "fused-1d",
    "fused-2d",
    "fused-3d",
    "tiled-2d",
    "sharded-2d",
)

# the derived cdf53 single-level 1D certificate: |x| <= 2^29 - 1 keeps
# every lifting intermediate inside int32.  Pinned like TABLE2_EXACT —
# a change means the tracer (or the scheme registry) changed semantics.
CDF53_SAFE_ABS_1D_L1 = 536870911

# checked=False must be the seed's code path: one predicate, no tracing.
# The regression this catches (the disabled path starting to run the
# host interval walk) measures 5x+, so the bound is generous to CI noise.
MAX_CHECKED_OFF_OVERHEAD = 2.0

# fault taxonomy (repro/resilience/inject.py FAULT_CLASSES) and the
# outcome the degradation ladder must deliver for each: recover
# bit-exactly, degrade to a slower-but-correct path, fail with a typed
# error, or keep the previous checkpoint intact.  "silent" is never
# acceptable — that is the silent-corruption failure mode the whole
# resilience layer exists to rule out.
EXPECTED_RECOVERY = {
    "bit-flip": ("recovered",),
    "truncation": ("typed-error",),
    "save-crash": ("previous-intact",),
    "pallas-failure": ("typed-error",),
    "stuck-neighbor": ("typed-error",),
    "deadline-miss": ("typed-error",),
}

# Table 2: the paper's (5,3) op counts must hold exactly
TABLE2_EXACT = (
    ("table2.ls.adders", 4.0),
    ("table2.ls.shifters", 2.0),
    ("table2.ls.multipliers", 0.0),
    ("table2.scheme.cdf53.adders", 4.0),
    ("table2.scheme.cdf53.shifters", 2.0),
)

# speedup floors.  The interpret baselines are 10-100x slower than the
# compiled paths, so 1.0 is a safe floor there.  The per-level pyramid
# and per-axis 3D comparisons are compiled-vs-compiled: on CPU both
# sides are jitted XLA and the true ratio is ~1.0, so those floors sit
# at 0.5 — the regression they exist to catch (falling off the compiled
# path onto the interpreter or an eager per-call path) measures 10x+.
MIN_FUSED_VS_INTERPRET = 1.0
MIN_PYRAMID_SPEEDUP = 0.5
MIN_3D_SPEEDUP = 0.5


def parse_rows(text: str) -> Dict[str, str]:
    """``name,value,notes`` CSV rows (benchmarks/run.py output) -> dict."""
    rows: Dict[str, str] = {}
    for line in text.splitlines():
        parts = line.strip().split(",", 2)
        if len(parts) >= 2 and parts[0] != "name":
            rows[parts[0]] = parts[1]
    return rows


def check_table2(rows: Dict[str, str]) -> List[str]:
    fails = []
    for key, want in TABLE2_EXACT:
        if key not in rows:
            fails.append(f"{key}: row missing from benchmark output")
            continue
        got = float(rows[key])
        if got != want:
            fails.append(f"{key}: got {got}, want {want}")
    scheme_mul_keys = [
        k
        for k in rows
        if k.startswith("table2.scheme.") and k.endswith(".multipliers")
    ]
    if not scheme_mul_keys:
        fails.append("no per-scheme table2 rows emitted")
    for key in scheme_mul_keys:
        if float(rows[key]) != 0.0:
            fails.append(f"{key}: got {rows[key]}, want 0 (multiplierless)")
    return fails


def check_schema(bench: dict) -> List[str]:
    """Structural validation of the BENCH_kernels.json payload.

    The tier-1 suite runs this against the checked-in file so a broken
    emission (missing section, dropped key, absent scheme row) fails in
    unit tests, not only in smoke.
    """
    fails = []
    for key in ("platform", "default_backend", "bit_exact"):
        if key not in bench:
            fails.append(f"bench payload missing top-level key {key!r}")
    for section, keys in REQUIRED_SECTIONS.items():
        if section not in bench:
            fails.append(f"bench payload missing section {section!r}")
            continue
        for key in keys:
            if key not in bench[section]:
                fails.append(f"bench section {section!r} missing key {key!r}")
    for section in ("smooth", "noisy"):
        row = bench.get("codec", {}).get(section, {})
        for key in ("raw_bytes", "wz_rice_bytes", "zlib_bytes",
                    "ratio_vs_zlib"):
            if not isinstance(row, dict) or key not in row:
                fails.append(f"bench codec.{section} missing key {key!r}")
    for holder, label, row_keys in (
        (bench.get("schemes", {}), "schemes",
         ("bit_exact", "multipliers_per_pair")),
        (bench.get("3d", {}).get("schemes", {}), "3d.schemes",
         ("bit_exact",)),
        (bench.get("codec", {}).get("lossless", {}), "codec.lossless", ()),
    ):
        for need in REQUIRED_SCHEMES:
            if need not in holder:
                fails.append(f"bench {label} missing row for {need!r}")
        for name, row in holder.items():
            for key in row_keys:
                if not isinstance(row, dict) or key not in row:
                    fails.append(f"bench {label}[{name!r}] missing {key!r}")
    return fails


def check_kernels(bench: dict) -> List[str]:
    """Behavioural gates over the kernel-engine payload (schema-valid)."""
    fails = []
    if not bench["bit_exact"]:
        fails.append("kernel outputs diverged from the kernels/ref oracle")

    # per-scheme engine rows: every registered scheme must round-trip
    # bit-exactly through the fused 1D + 2D engines, multiplierlessly
    for name, row in bench["schemes"].items():
        if not row["bit_exact"]:
            fails.append(f"scheme {name}: engine round-trip diverged")
        # key presence is guaranteed by check_schema (gate_failures stops
        # on any schema failure before reaching the behavioural gates)
        if row["multipliers_per_pair"] != 0:
            fails.append(f"scheme {name}: ledger shows multiplies")

    for section in ("1d_multilevel", "2d"):
        s = bench[section]["speedup_fused_vs_interpret"]
        if s <= MIN_FUSED_VS_INTERPRET:
            fails.append(f"{section}: fused compiled path no faster ({s}x)")

    # tiled engine: a budget-sized image must never silently leave the
    # Pallas path where Pallas IS the platform default (TPU; CPU defaults
    # to xla and GPU deliberately stays on xla until the Triton lowering
    # is validated — see kernels/backend.py _PALLAS_DEFAULT)
    large = bench["2d_large"]
    if bench["default_backend"] == "pallas":
        if large["plan"] != "tiled-pallas":
            fails.append(
                f"2d_large: {large['shape']} left the Pallas path on an "
                f"accelerator (plan={large['plan']})"
            )
    if not large["bit_exact"]:
        fails.append("2d_large: tiled transform diverged from the oracle")

    pyr = bench["2d_pyramid"]
    if not pyr["bit_exact"]:
        fails.append("2d_pyramid: fused pyramid diverged from the oracle")
    if pyr["speedup_fused_vs_per_level"] < MIN_PYRAMID_SPEEDUP:
        fails.append(
            "2d_pyramid: fused pyramid regressed vs per-level dispatch "
            f"({pyr['speedup_fused_vs_per_level']}x)"
        )
    return fails


def check_3d(bench: dict) -> List[str]:
    """Gates over the fused 3D engine section."""
    fails = []
    vol = bench["3d"]
    if not vol["bit_exact"]:
        fails.append("3d: fused volume transform diverged from the oracle")
    for name, row in vol["schemes"].items():
        if not row["bit_exact"]:
            fails.append(f"3d scheme {name}: volume round-trip diverged")
    if vol["speedup_fused_vs_per_axis"] < MIN_3D_SPEEDUP:
        fails.append(
            "3d: fused volume engine regressed vs per-axis dispatch "
            f"({vol['speedup_fused_vs_per_axis']}x)"
        )
    # video-scale volumes must stay on Pallas (slab engine) where Pallas
    # is the platform default — the 3D analogue of the 2d_large gate
    if bench["default_backend"] == "pallas":
        plan = bench["3d_large"]["plan"]
        if plan != "slab-pallas":
            fails.append(
                f"3d_large: {bench['3d_large']['shape']} left the Pallas "
                f"path on an accelerator (plan={plan})"
            )
    return fails


def check_codec(bench: dict) -> List[str]:
    """Gates over the entropy-codec section.

    Losslessness is the codec's contract: every registered scheme must
    round-trip its 1D/2D/3D pyramids bit-exactly through the WZRC
    container.  The ratio gate pins the acceptance claim — wz-rice
    checkpoint leaves beat plain-zlib bytes on smooth checkpoint-like
    tensors (and on incompressible fp32 noise, where zlib gets nothing
    while quantize+Rice halves the payload before entropy coding)."""
    fails = []
    codec = bench["codec"]
    for name, ok in codec["lossless"].items():
        if not ok:
            fails.append(f"codec scheme {name}: container roundtrip diverged")
    for section in ("smooth", "noisy"):
        row = codec[section]
        if row["wz_rice_bytes"] > row["zlib_bytes"]:
            fails.append(
                f"codec {section}: wz-rice ({row['wz_rice_bytes']}B) lost "
                f"to plain zlib ({row['zlib_bytes']}B)"
            )
    for key in ("encode_mbps", "decode_mbps"):
        if codec[key] <= 0:
            fails.append(f"codec {key}: non-positive throughput ({codec[key]})")
    return fails


def check_resilience(bench: dict) -> List[str]:
    """Gates over the fault-injection/recovery section.

    Pins the chaos invariant at the bench layer: the parity group costs
    exactly one band (a real fraction of the container, never free and
    never a doubling), a single damaged band heals bit-exactly, and
    every fault class in the taxonomy lands on its expected outcome."""
    fails = []
    res = bench["resilience"]
    ratio = res["parity_overhead_ratio"]
    if not (isinstance(ratio, (int, float)) and 0 < ratio < 1):
        fails.append(
            f"resilience: parity_overhead_ratio {ratio!r} outside (0, 1) — "
            "the XOR group must cost one band, not nothing or everything"
        )
    if not res["single_band_recovery"]:
        fails.append(
            "resilience: single damaged band did NOT heal from parity"
        )
    recovery = res["recovery"]
    for cls, allowed in EXPECTED_RECOVERY.items():
        if cls not in recovery:
            fails.append(f"resilience: fault class {cls!r} missing")
        elif recovery[cls] not in allowed:
            fails.append(
                f"resilience: {cls} outcome {recovery[cls]!r}, "
                f"expected one of {allowed}"
            )
    for cls in recovery:
        if cls not in EXPECTED_RECOVERY:
            fails.append(
                f"resilience: unknown fault class {cls!r} emitted "
                "(taxonomy and gate must move together)"
            )
    return fails


def check_ranges(bench: dict) -> List[str]:
    """Gates over the range-certificate / checked-arithmetic section.

    Pins the overflow-safety invariant: the certificates stay derived
    (the cdf53 value is pinned exactly; all are positive and shrink as
    dimensions multiply the cascade depth), EVERY engine's checked mode
    turns a wrap-capable input into a typed error, certified inputs
    round-trip bit-exactly under checking, and the disabled path costs
    nothing."""
    fails = []
    r = bench["ranges"]
    certs = r["certificates"]
    for need in REQUIRED_SCHEMES:
        if need not in certs:
            fails.append(f"ranges: certificate row missing for {need!r}")
    for name, row in certs.items():
        for key in ("safe_abs_1d_l1", "safe_abs_2d_l2", "int16_levels_3d"):
            if key not in row:
                fails.append(f"ranges certificate [{name!r}] missing {key!r}")
        if "safe_abs_1d_l1" in row and "safe_abs_2d_l2" in row:
            if not (0 < row["safe_abs_2d_l2"] <= row["safe_abs_1d_l1"]):
                fails.append(
                    f"ranges {name}: certificates not positive-monotone "
                    f"(1d_l1={row['safe_abs_1d_l1']}, "
                    f"2d_l2={row['safe_abs_2d_l2']})"
                )
    got = certs.get("cdf53", {}).get("safe_abs_1d_l1")
    if got != CDF53_SAFE_ABS_1D_L1:
        fails.append(
            f"ranges cdf53: derived certificate {got} != pinned "
            f"{CDF53_SAFE_ABS_1D_L1} — the interval tracer changed meaning"
        )
    wrap = r["wraparound"]
    for eng in CHECKED_ENGINES:
        if eng not in wrap:
            fails.append(f"ranges: engine {eng!r} missing from wraparound")
        elif wrap[eng] != "typed-error":
            fails.append(
                f"ranges {eng}: checked mode outcome {wrap[eng]!r} on a "
                "wrapping input — overflow passed silently"
            )
    for eng in wrap:
        if eng not in CHECKED_ENGINES:
            fails.append(
                f"ranges: unknown engine {eng!r} emitted (engine list and "
                "gate must move together)"
            )
    if not r["roundtrip_exact"]:
        fails.append(
            "ranges: certificate-respecting input did not round-trip "
            "bit-exactly under checked mode"
        )
    off = r["overhead_off_x"]
    if not (isinstance(off, (int, float)) and 0 < off <= MAX_CHECKED_OFF_OVERHEAD):
        fails.append(
            f"ranges: checked-off overhead {off!r}x exceeds "
            f"{MAX_CHECKED_OFF_OVERHEAD}x — the disabled path is not free"
        )
    if not (isinstance(r["overhead_on_x"], (int, float)) and r["overhead_on_x"] > 0):
        fails.append(
            f"ranges: overhead_on_x {r['overhead_on_x']!r} non-positive"
        )
    return fails


def check_serve(bench: dict) -> List[str]:
    """Gates over the serve-tier section.

    Pins the PR 8 serve invariants at the bench layer: the compiled-
    executable cache serves a mixed-bucket workload with NO miss after
    warmup (an admission or bucket switch that recompiles shows up here
    as a hit rate below 1.0), the batch-level response encode actually
    amortizes the coder (>= 1.5x the per-request loop), and the
    progressive thumbnail tier reads a strict fraction of the stored
    container's bytes (partial decode is measurably partial)."""
    fails = []
    srv = bench["serve"]
    if srv["requests_per_s"] <= 0:
        fails.append(
            f"serve: non-positive throughput ({srv['requests_per_s']} req/s)"
        )
    if srv["p99_ms"] <= 0:
        fails.append(f"serve: non-positive p99 latency ({srv['p99_ms']} ms)")
    if srv["cache_hit_rate"] != 1.0:
        fails.append(
            f"serve: executable cache hit rate {srv['cache_hit_rate']} after "
            "warmup — something recompiled under the mixed-bucket workload"
        )
    n_buckets = len(srv["buckets"])
    if srv["compiles"] > n_buckets:
        fails.append(
            f"serve: {srv['compiles']} compiles for {n_buckets} buckets — "
            "more than one executable per bucket"
        )
    s = srv["batch_encode_speedup"]
    if not (isinstance(s, (int, float)) and s >= MIN_BATCH_ENCODE_SPEEDUP):
        fails.append(
            f"serve: batch-level encode speedup {s!r}x below the "
            f"{MIN_BATCH_ENCODE_SPEEDUP}x floor vs the per-request loop"
        )
    frac = srv["thumbnail_bytes_fraction"]
    if not (isinstance(frac, (int, float)) and 0 < frac < 1):
        fails.append(
            f"serve: thumbnail tier read {frac!r} of the container — "
            "progressive decode is not reading a strict byte subset"
        )
    return fails


def check_obs(bench: dict) -> List[str]:
    """Gates over the observability section.

    Pins the obs-layer acceptance claims at the bench layer: the
    instrumentation costs at most MAX_OBS_OVERHEAD on the serve
    throughput workload (vs the ``obs.disabled()`` bare arm), every
    subsystem shows up with live metric series AND recorded spans after
    one seeded chaos run, and that run produces at least one event of
    every kind in the taxonomy — a silent instrumentation regression
    (a subsystem dropping off the registry, an event site going dark)
    fails here, not in production."""
    fails = []
    o = bench["observability"]
    ratio = o["overhead_x"]
    if not (isinstance(ratio, (int, float)) and 0 < ratio <= MAX_OBS_OVERHEAD):
        fails.append(
            f"observability: instrumentation overhead {ratio!r}x exceeds "
            f"{MAX_OBS_OVERHEAD}x on the serve throughput workload — "
            "too expensive to leave on"
        )
    for key in ("metric_subsystems", "span_subsystems"):
        got = set(o[key])
        missing = [s for s in OBS_SUBSYSTEMS if s not in got]
        if missing:
            fails.append(
                f"observability: {key} missing {missing} after the "
                "seeded chaos run (instrumentation went dark)"
            )
    for kind in OBS_EVENT_KINDS:
        if o["events"].get(kind, 0) < 1:
            fails.append(
                f"observability: chaos run produced no {kind} — that "
                "event site stopped emitting"
            )
    if o["event_total"] < sum(o["events"].values()):
        fails.append(
            f"observability: event_total {o['event_total']} below the "
            "in-ring count — the unbounded total regressed"
        )
    return fails


def gate_failures(rows: Dict[str, str], bench: dict) -> List[str]:
    """Every gate failure, most structural first.  ANY schema failure
    stops before the behavioural gates: those index the payload freely
    and would otherwise die on a KeyError instead of reporting the
    failure list this module promises."""
    schema_fails = check_schema(bench)
    if schema_fails:
        return check_table2(rows) + schema_fails
    return (
        check_table2(rows)
        + check_kernels(bench)
        + check_3d(bench)
        + check_codec(bench)
        + check_resilience(bench)
        + check_ranges(bench)
        + check_serve(bench)
        + check_obs(bench)
    )


def summary(bench: dict) -> str:
    large = bench["2d_large"]
    pyr = bench["2d_pyramid"]
    vol = bench["3d"]
    return (
        "SMOKE OK: fused-vs-interpret speedups "
        f"1d={bench['1d_multilevel']['speedup_fused_vs_interpret']}x "
        f"2d={bench['2d']['speedup_fused_vs_interpret']}x; "
        f"2d_large plan={large['plan']} fwd={large['fwd_us']}us; "
        f"pyramid fused/per-level={pyr['speedup_fused_vs_per_level']}x; "
        f"3d fused/per-axis={vol['speedup_fused_vs_per_axis']}x "
        f"plan={vol['plan']}; "
        f"batched {bench['2d_batched']['images_per_s']} img/s; "
        f"schemes bit-exact: {sorted(bench['schemes'])}; "
        f"codec lossless {sorted(bench['codec']['lossless'])} "
        f"rice-vs-zlib {bench['codec']['smooth']['ratio_vs_zlib']}x smooth "
        f"/ {bench['codec']['noisy']['ratio_vs_zlib']}x noisy; "
        f"resilience parity={bench['resilience']['parity_overhead_ratio']} "
        f"band-heal={bench['resilience']['single_band_recovery']}; "
        f"ranges checked={len(bench['ranges']['wraparound'])} engines "
        f"typed, off-cost={bench['ranges']['overhead_off_x']}x; "
        f"serve {bench['serve']['requests_per_s']} req/s "
        f"p99={bench['serve']['p99_ms']}ms "
        f"hit-rate={bench['serve']['cache_hit_rate']} "
        f"batch-enc={bench['serve']['batch_encode_speedup']}x "
        f"thumb={bench['serve']['thumbnail_bytes_fraction']}; "
        f"obs overhead={bench['observability']['overhead_x']}x "
        f"subsystems={len(bench['observability']['metric_subsystems'])} "
        f"events={bench['observability']['event_total']} "
        f"(backend={bench['default_backend']}, platform={bench['platform']})"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--csv", required=True, help="benchmarks/run.py CSV output")
    ap.add_argument(
        "--bench", default="BENCH_kernels.json",
        help="machine-readable kernels payload",
    )
    args = ap.parse_args(argv)
    with open(args.csv) as fh:
        rows = parse_rows(fh.read())
    with open(args.bench) as fh:
        bench = json.load(fh)
    fails = gate_failures(rows, bench)
    if fails:
        print("SMOKE FAILED:")
        for f in fails:
            print("  -", f)
        return 1
    print(summary(bench))
    return 0


if __name__ == "__main__":
    sys.exit(main())
