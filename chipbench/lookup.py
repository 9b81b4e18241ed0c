"""Finds a configuration, a traffic mix, a cell's data, a model family or
a per-layer metric reader by the name ``BENCHMARK.json`` (or, for a
family, the configuration file) gives it.

Each lives in a file of its own under one of the search roots:

    configs/<config>.json     sizes, source, deployment, serving settings,
                              and the ``family`` whose code runs it
    traffic/<mix>.json        parameters for ``traffic.schedule``
    cells/<workload>.json     the cell's offered rate and drain limit
    families/<family>.py      the model: ``make_weights``, ``engine``,
                              ``widest_gap``, ``layer_matmul_params``,
                              ``kv_bytes_per_token`` (``families/qwen2.py``)
    metrics/<metric>.py       ``read(rec) -> float | None``

Adding one is adding a file and an entry; no file that is there changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Lookup:
    def __init__(self, roots: Sequence[str] = (HERE,),
                 benchmark: Optional[str] = None):
        self.roots = list(roots)
        with open(benchmark or os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self._families: Dict[str, ModuleType] = {}

    def _path(self, kind: str, name: str, ext: str) -> str:
        for root in self.roots:
            p = os.path.join(root, kind, name + ext)
            if os.path.isfile(p):
                return p
        raise FileNotFoundError(f"no {kind}/{name}{ext} under {self.roots}")

    def _json(self, kind: str, name: str) -> Dict:
        with open(self._path(kind, name, ".json")) as f:
            return json.load(f)

    def workload(self, name: str) -> Dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.bench['workloads']]}")

    def config(self, name: str) -> Dict:
        return self._json("configs", name)

    def mix(self, name: str) -> Dict:
        return self._json("traffic", name)

    def cell(self, name: str) -> Dict:
        return self._json("cells", name)

    def cell_path(self, name: str) -> str:
        return self._path("cells", name, ".json")

    def metrics(self, workload: str, kind: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` entries ``workload``
        reports: those without a ``workloads`` key, and those that list
        it."""
        return [m for m in self.bench[kind]
                if workload in m.get("workloads", [workload])]

    def family(self, name: str) -> ModuleType:
        """The model code ``families/<name>.py``, loaded once."""
        if name not in self._families:
            self._families[name] = _load("chipbench_family", self._path(
                "families", name, ".py"), name)
        return self._families[name]

    def reader(self, metric: str) -> Callable:
        return _load("chipbench_metric", self._path("metrics", metric, ".py"),
                     metric).read


def _load(prefix: str, path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
