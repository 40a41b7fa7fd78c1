"""The generator and the pools: deterministic per seed, right shapes."""
import numpy as np
import pytest

from bench import data, registry, traffic, work

BIG_SEED = 2**31 + 12345


def _small(name, **kw):
    cfg = registry.config(name)
    cfg.update(kw)
    return cfg


@pytest.mark.parametrize("name,shapes,bits", [
    ("dicom-ct-512", [[96, 80]], 12),
    ("j2k-lossless-4k", [[64, 64], [64, 48], [40, 60], [36, 64]], 16),
])
def test_pool_is_int16_of_the_request_shapes_and_fixed_by_the_seed(name, shapes, bits):
    cfg = _small(name, request_shapes=shapes, pool_size=2 * len(shapes))
    a, b = data.make_pool(cfg, BIG_SEED), data.make_pool(cfg, BIG_SEED)
    c = data.make_pool(cfg, BIG_SEED + 1)
    assert [img.shape for img in a] == [tuple(s) for s in shapes] * 2
    assert all(img.dtype == np.int16 for img in a)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    lo = min(int(img.min()) for img in a)
    hi = max(int(img.max()) for img in a)
    assert -(1 << (bits - 1)) <= lo and hi <= (1 << (bits - 1)) - 1
    assert hi - lo > (1 << (bits - 2))  # the samples use their stored range


def test_ct_phantom_holds_air_tissue_lung_and_bone():
    cfg = {"samples": {"kind": "ct_phantom", "noise_hu": 12.0}, "bits_stored": 12}
    img = registry.sample("ct_phantom").make((256, 256), data.rng_for(1), cfg)
    hu = img.astype(int)
    assert np.mean(hu < -900) > 0.2  # air around the body
    assert np.mean(np.abs(hu - 40) < 50) > 0.1  # soft tissue
    assert np.mean((hu > -900) & (hu < -700)) > 0.02  # lungs
    assert np.mean(hu > 600) > 0.002  # bone


def test_open_arrivals_replay_one_schedule_and_the_seed_picks_the_images():
    mix = {"pattern": "open", "rate_per_s": 9.0, "schedule_seed": 1}
    a = traffic.arrivals(mix, 64, BIG_SEED, 30.0)
    b = traffic.arrivals(mix, 64, BIG_SEED, 30.0)
    c = traffic.arrivals(mix, 64, BIG_SEED + 7, 30.0)
    assert a == b and a != c
    assert [t for t, _ in a] == [t for t, _ in c]
    assert a[0][0] == 0.0 and all(t < 30.0 for t, _ in a)
    assert abs(len(a) - 270) < 30
    gaps = np.diff([t for t, _ in a])
    assert np.mean(gaps) == pytest.approx(1 / 9.0, rel=0.1)
    assert np.std(gaps) == pytest.approx(1 / 9.0, rel=0.2)  # exponential: sd = mean
    other = traffic.arrivals(dict(mix, schedule_seed=2), 64, BIG_SEED, 30.0)
    assert [t for t, _ in other] != [t for t, _ in a]


def test_closed_order_cycles_the_pool_and_read_order_is_seeded():
    order = traffic.pool_order(4, BIG_SEED)
    first = [next(order) for _ in range(12)]
    assert all(sorted(first[i:i + 4]) == [0, 1, 2, 3] for i in (0, 4, 8))
    uni = traffic.read_order(64, BIG_SEED)
    again = traffic.read_order(64, BIG_SEED)
    xs = [next(uni) for _ in range(500)]
    assert xs == [next(again) for _ in range(500)] and set(xs) == set(range(64))
    other = traffic.read_order(64, BIG_SEED + 1)
    assert [next(other) for _ in range(500)] != xs
    with pytest.raises(FileNotFoundError):
        registry.pattern("bursty-unknown")


def test_minimal_bytes_count_samples_at_two_bytes_plus_coded_bytes():
    assert work.ingest_bytes(512 * 512, 1000) == 512 * 512 * 2 + 1000
    assert work.read_bytes(250.5, 100) == 450.5

    class Rec:
        def __init__(self, blob):
            self.blob = blob

    shared, other = b"x" * 100, b"y" * 30
    assert work.coded_bytes([Rec(shared), Rec(shared), Rec(other), Rec(None)]) == 130
    assert work.p95([1.0] * 19 + [100.0]) == 100.0
    assert work.p95([1.0] * 100) == 1.0
    assert work.p95([1.0] * 90 + [float("inf")] * 10) is None


def test_roofline_and_idle_shares_from_a_trace_summary():
    class Run:
        trace = {"busy_s": 2.0, "window_s": 8.0}
        peaks = {"hbm_bytes_per_s": 1e9}

    assert work.roofline_pct(1e9, Run) == pytest.approx(50.0)
    assert work.idle_pct(Run) == pytest.approx(75.0)
    Run.trace = {"busy_s": 0.0, "window_s": 8.0}
    assert work.roofline_pct(1e9, Run) is None and work.idle_pct(Run) is None
