"""Axial CT slices in Hounsfield units: air outside the body, soft tissue,
fat, lungs, vertebra and ribs as ellipses, with a smooth beam-hardening
shading and Gaussian noise of ``samples.noise_hu``, clipped to the signed
range of ``bits_stored``."""
import numpy as np


def _fill(img, cy, cx, ry, rx, value, angle=0.0, mask_out=None):
    """Set the ellipse (centre and radii as fractions of the frame, from
    its middle) to ``value``, touching only its bounding box."""
    h, w = img.shape
    r = max(ry, rx)
    y0, y1 = max(int((cy - r + 0.5) * h), 0), min(int((cy + r + 0.5) * h) + 2, h)
    x0, x1 = max(int((cx - r + 0.5) * w), 0), min(int((cx + r + 0.5) * w) + 2, w)
    yy = (np.arange(y0, y1, dtype=np.float32) / h - 0.5 - cy)[:, None]
    xx = (np.arange(x0, x1, dtype=np.float32) / w - 0.5 - cx)[None, :]
    c, s = np.cos(angle), np.sin(angle)
    inside = ((xx * c + yy * s) / rx) ** 2 + ((yy * c - xx * s) / ry) ** 2 <= 1.0
    img[y0:y1, x0:x1][inside] = value
    if mask_out is not None:
        mask_out[y0:y1, x0:x1] |= inside


def make(shape, rng: np.random.Generator, config) -> np.ndarray:
    """One slice (int16), body jittered by ``rng``."""
    noise_hu, bits_stored = config["samples"]["noise_hu"], config["bits_stored"]
    h, w = shape
    j = rng.uniform(-1.0, 1.0, 12).astype(np.float32)
    img = np.full(shape, -1000.0, np.float32)  # air
    _fill(img, 0.02 * j[0], 0.0, 0.33 + 0.02 * j[1], 0.42 + 0.02 * j[2], -90.0)  # fat
    inner = np.zeros(shape, bool)
    _fill(img, 0.02 * j[0], 0.0, 0.29 + 0.02 * j[1], 0.37 + 0.02 * j[2], 40.0,
          mask_out=inner)  # soft tissue
    for side in (-1, 1):
        _fill(img, -0.03 + 0.02 * j[3], side * (0.17 + 0.01 * j[4]),
              0.21 + 0.02 * j[5], 0.11 + 0.01 * j[6], -830.0, side * 0.15)  # lung
    _fill(img, 0.20 + 0.01 * j[7], 0.0, 0.05, 0.045, 1000.0)  # vertebra
    _fill(img, 0.20 + 0.01 * j[7], 0.0, 0.025, 0.02, 250.0)  # marrow
    _fill(img, -0.02, 0.02 * j[8], 0.05, 0.06, 45.0 + 10.0 * j[9])  # heart
    for k in range(8):  # ribs
        a = np.pi * (0.15 + 0.7 * k / 7)
        _fill(img, 0.27 * np.sin(a), 0.361 * np.cos(a), 0.012, 0.02, 700.0, a)
    shade = 15.0 * (np.cos(np.pi * (np.arange(w, dtype=np.float32) / w - 0.5)) - 0.5)
    img += np.where(inner, shade[None, :] * (1 + 0.2 * j[10]), 0.0).astype(np.float32)
    img += rng.standard_normal(shape, dtype=np.float32) * np.float32(noise_hu)
    lo, hi = -(1 << (bits_stored - 1)), (1 << (bits_stored - 1)) - 1
    return np.clip(np.rint(img), lo, hi).astype(np.int16)
