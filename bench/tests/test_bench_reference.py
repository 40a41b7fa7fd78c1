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
        assert reference.band_shapes(h, w, levels) == want


def test_any_damage_reads_as_every_sample_wrong_or_as_the_changed_samples():
    rng = np.random.default_rng(3)
    img = rng.integers(-2048, 2048, (32, 32)).astype(np.int16)
    blob = _container([img], 2)

    def wrong(data, index, image, levels=2):
        return reference.mismatches(reference.decode_or_none(data, levels=levels), index, image)

    flipped = bytearray(blob)
    flipped[-3] ^= 0x10
    assert wrong(bytes(flipped), 0, img) == img.size  # a band CRC fails
    assert wrong(blob[:-1], 0, img) == img.size  # truncated
    assert wrong(blob[:30], 0, img) == img.size  # the header cut short
    assert wrong(None, 0, img) == img.size  # no answer
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
