"""Seeded image pools: the samples every request of a run draws from.

Made at set-up, in bulk with numpy, and held on the host as int16 (the
stored sample type of both deployments); the engine's int32 batch
assembly stays in the timed path.  The configuration's ``samples.kind``
names the sample model, ``samples/<kind>.py``.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

from bench import registry


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A generator for ``seed`` (any size) and a stream tag."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def make_pool(config: Dict, seed: int, root: Path = registry.ROOT) -> List[np.ndarray]:
    """The configuration's pool of ``pool_size`` images for ``seed``.

    Image ``i`` has shape ``request_shapes[i % len(request_shapes)]``, so
    every shape is in the pool in equal numbers.
    """
    model = registry.sample(config["samples"]["kind"], root)
    shapes: Sequence = config["request_shapes"]
    return [
        model.make(tuple(shapes[i % len(shapes)]), rng_for(seed, 1, i), config)
        for i in range(config["pool_size"])
    ]
