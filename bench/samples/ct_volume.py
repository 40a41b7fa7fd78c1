"""A CT series in Hounsfield units, continuous from slice to slice.

One series covers a chest-abdomen range from the lung apices down, at
``SLICE_MM`` between slices (the configuration's assumed spacing); sizes
in the plane are fractions of the frame, which at 512 pixels of 0.7 mm
is a 358 mm field of view.  Every structure is a solid whose section changes
smoothly with z:

- an elliptic body (fat, with soft tissue inside) whose section swells
  and narrows along the series;
- two lungs, ellipsoids over the upper part, whose bases are cut by a
  dome-shaped diaphragm: the liver's ellipsoid on the right and a
  soft-tissue one (stomach and spleen) on the left;
- a heart ellipsoid between the lungs;
- vertebral bodies at a pitch of about 25 mm with discs between;
- ribs that run round the body from the spine and fall as they go, so
  their sections move along the body's outline from slice to slice.

On top, the beam-hardening shade of ``ct_phantom`` inside the body and
independent Gaussian noise of ``samples.noise_hu`` per voxel, clipped to
the signed range of ``bits_stored``.  The seed moves every size and
position, as another patient would.
"""
from pathlib import Path

import numpy as np

from bench import registry

SLICE_MM = 1.25  # slice spacing
VERTEBRA_MM = 25.0  # vertebral pitch, a body and its disc
DISC_MM = 5.0

_fill = registry.sample("ct_phantom", Path(__file__).resolve().parents[1])._fill


def _section(z: float, zc: float, rz: float) -> float:
    """Scale of an ellipsoid's section at ``z``: 0 outside it."""
    t = 1.0 - ((z - zc) / rz) ** 2
    return float(np.sqrt(t)) if t > 0 else 0.0


def _ellipse(h: int, w: int, cy: float, ry: float, rx: float) -> np.ndarray:
    """Mask of the upright ellipse centred (cy, 0) in the frame, by row
    spans: the body's two sections, the largest fills of a slice."""
    y = (np.arange(h, dtype=np.float32) / h - 0.5 - cy) / ry
    span = rx * np.sqrt(np.maximum(1.0 - y * y, 0.0)) - (np.abs(y) > 1)
    x = np.abs(np.arange(w, dtype=np.float32) / w - 0.5)
    return x[None, :] <= span[:, None]


def make(shape, rng: np.random.Generator, config) -> np.ndarray:
    """One series (int16, (slices, rows, columns)) for this patient."""
    noise_hu, bits_stored = config["samples"]["noise_hu"], config["bits_stored"]
    d, h, w = shape
    j = rng.uniform(-1.0, 1.0, 16).astype(np.float32)
    body_y, body_rx, body_ry = 0.02 * j[0], 0.40 + 0.02 * j[1], 0.30 + 0.02 * j[2]
    lung_zc, lung_rz = 100.0 + 10.0 * j[3], 120.0 + 8.0 * j[4]
    liver_zc, spleen_zc = 240.0 + 15.0 * j[5], 250.0 + 15.0 * j[6]
    heart_zc, heart_x = 120.0 + 10.0 * j[7], 0.05 + 0.02 * j[8]
    spine_y, spine_phase = 0.20 + 0.01 * j[9], VERTEBRA_MM * (0.5 + 0.5 * j[10])
    rib_z0, rib_drop = -20.0 + 10.0 * j[11], 90.0 + 10.0 * j[12]
    gain = 1 + 0.2 * j[13]
    shade = 15.0 * (np.cos(np.pi * (np.arange(w, dtype=np.float32) / w - 0.5)) - 0.5)
    lo, hi = -(1 << (bits_stored - 1)), (1 << (bits_stored - 1)) - 1
    out = np.empty(shape, np.int16)
    for k in range(d):
        z = k * SLICE_MM
        swell = 1.0 + 0.06 * np.sin(np.pi * z / 300.0)
        rx, ry = body_rx * swell, body_ry * (1.0 + 0.05 * np.sin(np.pi * z / 220.0))
        inner = _ellipse(h, w, body_y, ry - 0.04, rx - 0.05)  # soft tissue
        img = np.where(
            inner, np.float32(40.0),
            np.where(_ellipse(h, w, body_y, ry, rx), np.float32(-90.0),  # fat
                     np.float32(-1000.0)),  # air
        )
        s = _section(z, lung_zc, lung_rz)
        if s > 0.02:
            for side in (-1, 1):
                _fill(img, body_y - 0.03, side * 0.17 * swell, 0.21 * s, 0.11 * s,
                      -830.0, side * 0.15)  # lung
        for zc, x, value in ((liver_zc, -0.12, 60.0), (spleen_zc, 0.14, 45.0)):
            s = _section(z, zc, 110.0)  # its top is the diaphragm's dome
            if s > 0.02:
                _fill(img, body_y, x, 0.2 * s, 0.17 * s, value)
        s = _section(z, heart_zc, 55.0)
        if s > 0.02:
            _fill(img, body_y - 0.04, heart_x, 0.12 * s, 0.14 * s, 45.0, 0.4)  # heart
        if (z + spine_phase) % VERTEBRA_MM < VERTEBRA_MM - DISC_MM:
            _fill(img, spine_y, 0.0, 0.05, 0.045, 1000.0)  # vertebral body
            _fill(img, spine_y, 0.0, 0.035, 0.03, 250.0)  # marrow
        else:
            _fill(img, spine_y, 0.0, 0.05, 0.045, 80.0)  # disc
        for n in range(12):  # rib n leaves the spine at rib_z0 + n pitches
            phi = np.pi * (z - rib_z0 - n * VERTEBRA_MM) / rib_drop
            if 0.15 < phi < 0.8 * np.pi:
                for side in (-1, 1):
                    _fill(img, body_y + 0.92 * ry * np.cos(phi),
                          side * 0.92 * rx * np.sin(phi), 0.012, 0.02, 700.0,
                          side * phi)
        img += np.where(inner, shade[None, :] * gain, 0.0).astype(np.float32)
        img += rng.standard_normal((h, w), dtype=np.float32) * np.float32(noise_hu)
        out[k] = np.clip(np.rint(img), lo, hi)
    return out
