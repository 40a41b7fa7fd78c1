"""The volume transform's share of its roofline, in %: the least HBM
bytes of the window's 3-D transforms at peak bandwidth, over the device
time of their program, ``jit_transform_3d``, in the trace.  The reader
of ``lift3d_roofline.vol``.

The bytes are counted from shapes, for each ``serve.step`` root of the
window (``spans.py``) with a 3-D bucket: the batch of ``batch_slots``
volumes the step transformed, each through ``levels`` levels,

- the level-0 input read once at its stored 2 B a sample;
- each level's eight bands written once at 4 B (int32, the program's
  band type);
- each later level's input, the all-lowpass band of the one before,
  read once at 4 B;

about 7.14 B a sample at 5 levels.  Lifting is a few shift-adds a
sample, so bytes bound the transform, not operations.

The share cannot pass 100 %: the count is at most what the program
itself moves through HBM.  Each level is a kernel dispatch of its own
whose operands and results live in HBM, so the program reads its whole
input (level 0 at 4 B, not 2), writes all eight bands of every level,
and reads each lowpass band back for the next level; the halo'd windows
the slab kernel gathers only add to that.  Where the program is not in
the trace (a program that names the volume's transform otherwise), or
the window's roots are not all in the span ring, there is no number.
"""
from bench import spans

PROGRAM = "jit_transform_3d"
SAMPLE_BYTES, BAND_BYTES = 2, 4


def least_bytes(shape, levels: int) -> int:
    """Least HBM bytes of one ``levels``-deep 3-D transform of ``shape``."""
    d, h, w = shape
    total = SAMPLE_BYTES * d * h * w
    for level in range(levels):
        n = d * h * w
        total += BAND_BYTES * n * (2 if level else 1)  # bands out (+ input in)
        d, h, w = d - d // 2, h - h // 2, w - w // 2
    return total


def read(run):
    device_s = dict(run.trace["device_ops"]).get(PROGRAM) if run.trace else None
    if not device_s or not run.peaks:
        return None
    w = spans.window("serve.step", run.obs_delta.get("serve.batches", 0))
    if not w:
        return None
    shapes = [tuple(int(s) for s in r.args.get("bucket", "").split("x")) for r in w.roots]
    least = run.config["batch_slots"] * sum(
        least_bytes(s, run.config["levels"]) for s in shapes if len(s) == 3
    )
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / device_s if least else None
