"""Program spans land on the profiler's host plane, nested as the tracer
nests them: one served batch and one full-fidelity read on the CPU
backend, under ``jax.profiler``, in a child process with a time limit of
its own (the profiler session is process-wide)."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
TIME_LIMIT_S = 240

CHILD = r"""
import json, sys
import numpy as np
import jax
from repro import obs
from repro.serve import ProgressiveServeRoute, TransformRequest, WaveletServeEngine

eng = WaveletServeEngine(buckets=[(32, 32)], batch_slots=2, levels=2,
                         encode_response=True)
eng.warmup()
rng = np.random.default_rng(0)
img = lambda: rng.integers(-100, 100, (32, 32), dtype=np.int32)
route = ProgressiveServeRoute()
for r in eng.run([TransformRequest(uid=i, image=img()) for i in range(2)]):
    route.store(r)
route.full(0)  # every shape compiled before the trace
eng.submit(TransformRequest(uid=2, image=img()))
obs.reset()
jax.profiler.start_trace(sys.argv[1])
eng.step()
route.full(1)
jax.profiler.stop_trace()
by_id = {s.span_id: s for s in obs.tracer.spans()}
print(json.dumps([
    [s.name, by_id[s.parent_id].name if s.parent_id in by_id else None]
    for s in by_id.values()
]))
"""


def test_program_spans_nest_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(SRC), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(tmp_path)], env=env, capture_output=True,
        text=True, timeout=TIME_LIMIT_S,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    parent_of = dict(map(tuple, json.loads(proc.stdout.strip().splitlines()[-1])))
    assert parent_of["serve.step"] is None and parent_of["serve.read"] is None
    assert parent_of["codec.encode_band"] == "codec.encode_pyramid"
    assert parent_of["codec.encode_pyramid"] == "serve.step"
    assert parent_of["codec.decode_band"] == "serve.read"

    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    intervals = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in parent_of:
                    start = int(ev.start_ns)
                    intervals.setdefault(ev.name, []).append(
                        (plane.name, start, start + int(ev.duration_ns)))
    for name in ("serve.step", "codec.encode_band", "serve.read", "codec.decode_band"):
        assert intervals.get(name), f"{name} is not in the profiler trace"
    for name, parent in parent_of.items():
        for plane, a, b in intervals.get(name, []):
            assert plane.startswith("/host:"), (name, plane)
            if parent is not None:
                assert any(pa <= a and b <= pb for _, pa, pb in intervals[parent]), (
                    f"{name} [{a}, {b}] lies in no {parent}")
