"""The plain reference decoder against the system's own codec on the CPU."""
import numpy as np
import pytest

from bench import reference


def _container(images, levels):
    import jax.numpy as jnp

    from repro.codec import container
    from repro.core import lifting

    pyr = lifting.dwt_fwd_2d_multi(
        jnp.asarray(np.stack(images).astype(np.int32)), levels=levels,
        mode="jpeg2000", scheme="cdf53",
    )
    return container.encode_batch(pyr, scheme="cdf53", mode="jpeg2000")


@pytest.mark.parametrize("shape,levels,batch", [
    ((64, 64), 3, 2), ((37, 53), 3, 3), ((16, 80), 2, 1), ((2, 9), 1, 2),
])
def test_reference_decodes_the_systems_containers_bit_exactly(shape, levels, batch):
    rng = np.random.default_rng(sum(shape) + levels)
    images = [rng.integers(-32768, 32768, shape).astype(np.int16) for _ in range(batch)]
    images[0][0, 0] = -32768  # the zigzag escape of the extreme sample
    blob = _container(images, levels)
    got = reference.decode(blob, levels=levels)
    assert got.shape == (batch,) + shape
    for i, img in enumerate(images):
        assert np.array_equal(got[i], img)
        assert reference.mismatches(got, i, img) == 0


def test_reference_band_geometry_matches_the_system():
    from repro.core import lifting

    for h, w, levels in [(512, 512, 5), (37, 53, 3), (4096, 3328, 5)]:
        ll, det = lifting.band_shapes_2d(h, w, levels)
        want = [tuple(ll)] + [tuple(s) for lvl in det for s in lvl]
        assert reference.band_shapes((h, w), levels) == want


def test_any_damage_reads_as_every_sample_wrong_or_as_the_changed_samples():
    rng = np.random.default_rng(3)
    img = rng.integers(-2048, 2048, (32, 32)).astype(np.int16)
    blob = _container([img], 2)

    def wrong(data, index, image, levels=2):
        return reference.container_mismatches(data, [(index, image)], levels=levels)

    flipped = bytearray(blob)
    flipped[-3] ^= 0x10
    assert wrong(bytes(flipped), 0, img) == img.size  # a band CRC fails
    assert wrong(blob[:-1], 0, img) == img.size  # truncated
    assert wrong(blob[:30], 0, img) == img.size  # the header cut short
    assert reference.mismatches(None, 0, img) == img.size  # no answer
    assert wrong(blob, 1, img) == img.size  # no such row
    assert wrong(blob, 0, img, levels=3) == img.size  # another depth than stated
    assert wrong(blob, 0, img[:, :16]) == 0  # a padded request is cropped
    off_by_one = img.copy()
    off_by_one[5, 7] += 1
    assert wrong(blob, 0, off_by_one) == 1


def test_inverse_is_the_t800_synthesis_on_a_hand_example():
    # one level of 1-D 5/3 on [1, 2, 3, 4]: d = [0, 1], s = [1, 3]
    s = np.array([[1, 3]])
    d = np.array([[0, 1]])
    assert reference._inverse_axis(s, d, axis=-1).tolist() == [[1, 2, 3, 4]]


def _volume_container(volumes, levels, ndim=3):
    import jax.numpy as jnp

    from repro.codec import container
    from repro.core import lifting

    pyr = lifting.dwt_fwd_nd(
        jnp.asarray(np.stack(volumes).astype(np.int32)), levels=levels,
        mode="jpeg2000", scheme="cdf53", ndim=ndim,
    )
    return container.encode_batch(pyr, scheme="cdf53", mode="jpeg2000", ndim=ndim)


@pytest.mark.parametrize("shape,levels,batch", [
    ((8, 32, 32), 0, 1), ((7, 33, 20), 0, 2), ((1, 16, 16), 0, 3),
    ((8, 32, 32), 1, 2), ((7, 33, 20), 1, 1), ((8, 32, 32), 2, 3),
    ((7, 33, 20), 2, 2), ((8, 32, 32), 3, 1), ((7, 33, 20), 3, 3),
])
def test_reference_decodes_the_systems_volume_containers_bit_exactly(
        monkeypatch, shape, levels, batch):
    rng = np.random.default_rng(sum(shape) + 10 * levels + batch)
    volumes = [rng.integers(-32768, 32768, shape).astype(np.int16) for _ in range(batch)]
    volumes[-1][0, 0, 0] = -32768  # the zigzag escape of the extreme sample
    blob = _volume_container(volumes, levels)
    got = reference.decode(blob, levels=levels)
    assert got.shape == (batch,) + shape
    for i, vol in enumerate(volumes):
        assert np.array_equal(got[i], vol)
        assert reference.mismatches(got, i, vol) == 0
    monkeypatch.setattr(reference, "ROW_GROUP_SAMPLES", 1)  # one row at a time
    rows = list(reference.decode_rows(blob, levels=levels))
    assert [r for r, _ in rows] == list(range(batch))
    assert all(np.array_equal(a, vol) for (_, a), vol in zip(rows, volumes))
    pairs = [(i, vol) for i, vol in enumerate(volumes)]
    assert reference.container_mismatches(blob, pairs[::-1], levels=levels) == 0


def test_reference_volume_band_geometry_matches_the_system():
    from repro.core import lifting

    for shape, levels in [((256, 512, 512), 5), ((7, 33, 20), 3), ((8, 32, 32), 3),
                          ((300, 512, 512), 5), ((1, 16, 16), 0)]:
        approx, det = lifting.band_shapes_nd(shape, levels)
        want = [tuple(approx)] + [tuple(s) for lvl in det for s in lvl]
        assert reference.band_shapes(shape, levels) == want


def test_any_damage_to_a_volume_reads_as_every_sample_wrong_or_as_the_changed_samples():
    rng = np.random.default_rng(5)
    vols = [rng.integers(-2048, 2048, (6, 16, 20)).astype(np.int16) for _ in range(2)]
    blob = _volume_container(vols, 2)

    def wrong(data, index, image, levels=2):
        return reference.container_mismatches(data, [(index, image)], levels=levels)

    flipped = bytearray(blob)
    flipped[-3] ^= 0x10
    assert wrong(bytes(flipped), 1, vols[1]) == vols[1].size  # a band CRC fails
    assert wrong(blob[:-1], 0, vols[0]) == vols[0].size  # truncated
    assert wrong(blob[:30], 0, vols[0]) == vols[0].size  # the header cut short
    assert wrong(blob, 1, vols[0]) == np.count_nonzero(vols[0] != vols[1])  # the wrong row
    assert wrong(blob, 2, vols[0]) == vols[0].size  # no such row
    assert wrong(blob, None, vols[0]) == vols[0].size  # a batch where one was due
    assert wrong(blob, 0, vols[0], levels=1) == vols[0].size  # another depth than stated
    assert wrong(blob, 0, vols[0][:5, :, :13]) == 0  # a padded request is cropped
    assert wrong(blob, 0, np.zeros((7, 16, 20), np.int16)) == 7 * 16 * 20  # too deep
    assert wrong(blob, 0, vols[0][0]) == vols[0][0].size  # a slice where a volume was due
    off_by_one = vols[0].copy()
    off_by_one[3, 5, 7] += 1
    assert wrong(blob, 0, off_by_one) == 1
    # several images of one container: each against its row, all of them
    # wrong where the container does not decode
    pairs = [(0, off_by_one), (1, vols[1]), (5, vols[1])]
    assert reference.container_mismatches(blob, pairs, levels=2) == 1 + vols[1].size
    assert reference.container_mismatches(bytes(flipped), pairs, levels=2) == 3 * vols[1].size


@pytest.mark.parametrize("make", ["one_d", "nd_of_two_axes"])
def test_containers_of_other_kinds_are_refused(make):
    import jax.numpy as jnp

    from repro.codec import container
    from repro.core import lifting

    x = jnp.asarray(np.arange(2 * 16 * 16, dtype=np.int32).reshape(2, 16, 16))
    if make == "one_d":
        blob = container.encode_pyramid(
            lifting.dwt_fwd(x[0, 0], levels=2, mode="jpeg2000"), scheme="cdf53",
            mode="jpeg2000")
    else:
        blob = _volume_container(list(np.asarray(x)), 2, ndim=2)  # kind 3, ndim 2
    with pytest.raises(reference.ContainerError, match="version/kind/ndim"):
        reference.parse_header(blob)
    assert reference.container_mismatches(blob, [(0, np.zeros(16, np.int16))], levels=2) == 16
