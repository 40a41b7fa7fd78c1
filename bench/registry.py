"""Finds everything by name: cells, configurations, traffic, metrics, code.

Each lives in a file of its own under the benchmark's directory:

    configs/<config>.json    a deployment: sizes, guarantees, source; it
                             names its ``system`` and its sample model
                             (``samples.kind``)
    traffic/<mix>.json       a traffic mix: the parameters of its
                             ``pattern``, read by ``traffic.py``
    patterns/<pattern>.py    what a pattern drives: ``setup(run, system,
                             pool) -> state`` and ``window(run, state)``
    systems/<system>.py      the system under test: ``build(config)``
    samples/<kind>.py        a sample model: ``make(shape, rng, config)``
    metrics/<metric>.py      a reader: ``read(run) -> float | None``; a
                             metric split by the cells it moves
                             (``device_idle.open``) may share the reader
                             of its stem (``device_idle.py``)
    peaks.json               the chip peaks, keyed by ``device_kind``

so a later cell, mix, pattern, sample model, system or metric is a new
file and an entry in ``BENCHMARK.json``, and no edit of what is here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent
BENCHMARK = ROOT.parent / "BENCHMARK.json"


def _json(path: Path) -> Dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    return json.loads(path.read_text())


def benchmark(path: Path = BENCHMARK) -> Dict:
    return _json(path)


def cell(name: str, bench: Dict) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def config(name: str, root: Path = ROOT) -> Dict:
    return _json(root / "configs" / f"{name}.json")


def traffic(name: str, root: Path = ROOT) -> Dict:
    return _json(root / "traffic" / f"{name}.json")


def module(kind: str, name: str, root: Path = ROOT) -> ModuleType:
    """``<root>/<kind>/<name>.py``, loaded as a module of its own."""
    path = root / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pattern(name: str, root: Path = ROOT) -> ModuleType:
    return module("patterns", name, root)


def system(name: str, root: Path = ROOT) -> ModuleType:
    return module("systems", name, root)


def sample(kind: str, root: Path = ROOT) -> ModuleType:
    return module("samples", kind, root)


def metric(name: str, root: Path = ROOT) -> Callable[[Dict], Optional[float]]:
    """The ``read`` function of ``metrics/<name>.py``, or of the reader of
    the name's stem where the metric has none of its own."""
    stem = name.split(".", 1)[0]
    own = (root / "metrics" / f"{name}.py").is_file()
    return module("metrics", name if own or stem == name else stem, root).read


def peaks(device_kind: str, root: Path = ROOT) -> Dict:
    table = _json(root / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"(known: {sorted(table)}); add its published peaks with their source"
        )
    return table[device_kind]


def metrics_for(cell_name: str, bench: Dict, trace: bool) -> List[Dict]:
    """The metric entries a run of ``cell_name`` reports.

    ``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the
    per-layer ones; an entry with ``workloads`` applies to those cells
    only.
    """
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell_name in m.get("workloads", [cell_name])]
