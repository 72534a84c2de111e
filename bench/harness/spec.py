"""Cells, configurations, mixes and metric readers, found by name.

``BENCHMARK.json`` at the checkout's root names each cell's
configuration (``bench/configs/<config>.json``), its traffic
(``bench/traffic/<traffic>.json``) and the metrics it reports; a
per-layer metric's reader is ``bench/metrics/<metric>.py``.  Adding a
cell, a configuration, a mix or a metric adds files and entries only."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

from harness import traffic

BENCH = Path(__file__).resolve().parents[1]


class Spec:
    def __init__(self, bench_dir: Path = BENCH):
        self.bench = Path(bench_dir)
        self.root = self.bench.parent
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        return json.loads((self.bench / "configs" / f"{name}.json")
                          .read_text())

    def mix(self, name: str) -> Dict[str, Any]:
        return traffic.load_mix(self.bench / "traffic", name)

    def metrics(self, cell: str, kind: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports.
        A metric without ``workloads`` is reported in every cell (an
        end-to-end one) or in every cell that reports the end-to-end
        metric it moves (a per-layer one)."""
        e2e = {m["name"] for m in self.metrics(cell, "end_to_end")} \
            if kind == "per_layer" else set()
        out = []
        for m in self.doc[kind]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif kind == "end_to_end" or m["moves"] in e2e:
                out.append(m)
        return out

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Any]:
        path = self.bench / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read
