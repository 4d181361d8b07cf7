"""Where the benchmark's data lives, found by the names in
``BENCHMARK.json``.

    configs/<config>.json      one configuration: sizes, serving shape,
                               compression, source, reduced, assumed
    traffic/<traffic>.json     one traffic mix: parameters that the one
                               generator (``chipbench.traffic``) reads
    workloads/<cell>.json      what one cell adds to its mix: the offered
                               load and the limits of its check
    metrics/<metric>.py        one metric: ``read(run) -> float | None``
    models/<family>.py         a model family: weights, reference, work

A cell, a configuration, a traffic mix or a metric is added as new files
and new entries in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, Optional

#: benchmarks/chip, the directory this package sits in
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the checkout root, which holds BENCHMARK.json and src/
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


class LayoutError(Exception):
    """A name in BENCHMARK.json has no file, or the files disagree."""


class Layout:
    def __init__(self, root: str = HERE, bench_file: Optional[str] = None):
        self.root = root
        self.bench_file = bench_file or os.path.join(CHECKOUT,
                                                     "BENCHMARK.json")
        with open(self.bench_file) as f:
            self.bench = json.load(f)

    def _json(self, kind: str, name: str) -> Dict:
        path = os.path.join(self.root, kind, f"{name}.json")
        if not os.path.isfile(path):
            raise LayoutError(f"no {kind} file {path}")
        with open(path) as f:
            return json.load(f)

    def _module(self, kind: str, name: str):
        path = os.path.join(self.root, kind, f"{name}.py")
        if not os.path.isfile(path):
            raise LayoutError(f"no {kind} module {path}")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # ------------------------------------------------------------ lookups
    def cell(self, name: str) -> Dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise LayoutError(f"no workload {name!r} in {self.bench_file}")

    def config(self, name: str) -> Dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> Dict:
        return self._json("traffic", name)

    def workload(self, name: str) -> Dict:
        return self._json("workloads", name)

    def model(self, family: str):
        return self._module("models", family)

    def metric(self, name: str):
        return self._module("metrics", name)

    def metrics_for(self, cell: str, traced: bool) -> List[Dict]:
        """The cell's metrics of one kind: end-to-end ones untraced,
        per-layer ones traced; an entry with ``workloads`` applies to the
        cells it lists, one without to every cell."""
        group = "per_layer" if traced else "end_to_end"
        return [m for m in self.bench[group]
                if "workloads" not in m or cell in m["workloads"]]
