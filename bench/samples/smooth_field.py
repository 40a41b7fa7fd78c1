"""A smooth unsigned 16-bit field (bilinear upsampling of a coarse random
grid) plus Gaussian sensor noise of ``samples.noise``, DC-shifted to
signed as JPEG 2000 Annex G does."""
import numpy as np

GRID = 17


def make(shape, rng: np.random.Generator, config) -> np.ndarray:
    noise = config["samples"]["noise"]
    h, w = shape
    coarse = rng.uniform(4000.0, 61000.0, (GRID, GRID)).astype(np.float32)

    def interp(n):
        pos = np.linspace(0, GRID - 1, n, dtype=np.float32)
        i0 = np.minimum(pos.astype(np.int64), GRID - 2)
        f = pos - i0
        m = np.zeros((n, GRID), np.float32)
        m[np.arange(n), i0] = 1 - f
        m[np.arange(n), i0 + 1] = f
        return m

    img = interp(h) @ coarse @ interp(w).T
    img += rng.standard_normal(shape, dtype=np.float32) * noise
    return (np.clip(np.rint(img), 0, 65535) - 32768).astype(np.int16)
