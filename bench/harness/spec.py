"""Cells, configurations, mixes and metric readers, found by name.

``BENCHMARK.json`` at the checkout's root names each cell's
configuration (``bench/configs/<config>.json``), its traffic
(``bench/traffic/<traffic>.json``) and the metrics it reports; a
per-layer metric's reader is ``bench/metrics/<metric>.py``; the plain
reference a configuration names (``"reference"``) is
``bench/references/<reference>.py``.  Adding a cell, a configuration, a
mix, a metric or a reference adds files and entries only."""

from __future__ import annotations

import functools
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
        path = self.bench / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        if "reference" not in cfg:
            raise KeyError(f"{path}: no \"reference\" key; a configuration "
                           f"names the file under bench/references/ that "
                           f"holds its plain reference")
        return cfg

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
        return _load(path, "bench_metric_").read

    def reference(self, name: str) -> type:
        """The ``Reference`` class of ``bench/references/<name>.py``.

        Its contract: ``Reference(model, seed, control=False)`` makes the
        weights from ``seed`` as the program does for the configuration's
        ``model``; ``.logits(blocks, positions, cols=None)`` takes a list
        of (tokens (B, S), lengths (B,)) and, per block and row, the
        positions to read, and returns per block and row a float32
        array (positions, vocab), the first ``cols`` columns when set.
        ``control=True`` computes in the precision below the
        configuration's."""
        path = self.bench / "references" / f"{name}.py"
        return _load(path, "bench_reference_").Reference


@functools.lru_cache(maxsize=None)
def _load(path: Path, prefix: str):
    """The module at ``path``, loaded once per process."""
    name = path.stem.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(prefix + name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
