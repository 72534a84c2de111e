"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
per-program device time and the ``breakdown`` of a result line.

The core works on plain event tuples ``(name, start_ns, duration_ns)``,
so it can be checked by hand; ``read_xplane`` extracts them from a file
with nothing but JAX's ``ProfileData``:

  device ops      events of the ``XLA Ops`` line of each ``/device:TPU:N``
                  plane (falling back to ``XLA Modules``)
  device programs events of the ``XLA Modules`` line, named by the XLA
                  module without its ``(id)`` suffix, e.g.
                  ``jit_decode_and_sample``
  host spans      events of the host plane's threads whose name starts
                  with ``flexserve.`` (the program's TraceAnnotations) or
                  is a jitted call's dispatch (``PjitFunction(...)``)
  the window      the benchmark's own ``bench.window`` span, opened when
                  the per-layer counters are read at the start and closed
                  when they are read at the end; every reading is clipped
                  to it, so the trace's device times and the counters
                  cover the same stretch of time
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]           # name, start_ns, duration_ns

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"
_MODULE_ID = re.compile(r"\(\d+\)$")


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Sequence[Event], lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some operation ran."""
    total = 0.0
    for s, e in union([(s, s + d) for _, s, d in ops]):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            total += e - s
    return total


def program_times(modules: Sequence[Event], lo: float = -float("inf"),
                  hi: float = float("inf")) -> Dict[str, Dict[str, float]]:
    """Per program: executions that start in [lo, hi) and their total
    device nanoseconds."""
    out: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0, "ns": 0.0})
    for name, s, d in modules:
        if not lo <= s < hi:
            continue
        p = out[_MODULE_ID.sub("", name)]
        p["count"] += 1
        p["ns"] += d
    return dict(out)


def idle_gaps(ops: Sequence[Event], spans: Sequence[Event], lo: float,
              hi: float) -> Dict[str, float]:
    """Idle device nanoseconds in [lo, hi], each gap charged to the host
    span that overlaps it most (``host: no span`` where none does)."""
    busy = union([(max(s, lo), min(s + d, hi)) for _, s, d in ops
                  if s + d > lo and s < hi])
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted((s, s + d, n) for n, s, d in spans)
    out: Dict[str, float] = defaultdict(float)
    for gs, ge in gaps:
        best, label = (0.0, False), "host: no span"
        for s, e, n in spans:
            if s >= ge:
                break
            # a program's own span wins a tie with the dispatch inside it
            ov = (min(e, ge) - max(s, gs), n.startswith("flexserve."))
            if ov[0] > 0 and ov > best:
                best, label = ov, n
        out[label] += ge - gs
    return dict(out)


def top(d: Dict[str, float], k: int = 10, scale: float = 1e-9):
    return [[n, v * scale] for n, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def read_xplane(path: Path) -> Dict[str, object]:
    """Device ops and programs per device, and host spans, from a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[int, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    window: List[Event] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: [(e.name, e.start_ns, e.duration_ns)
                               for e in ln.events] for ln in plane.lines}
            modules = lines.get("XLA Modules", [])
            devices[int(m.group(1))] = {
                "ops": lines.get("XLA Ops") or modules,
                "modules": modules}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == WINDOW_SPAN:
                        window.append((e.name, e.start_ns, e.duration_ns))
                    elif (e.name.startswith("flexserve.")
                            or e.name.startswith("PjitFunction(")):
                        spans.append((e.name, e.start_ns, e.duration_ns))
    return {"devices": devices, "spans": spans, "window": window}


def reduce(raw: Dict[str, object], window_s: float) -> Dict[str, object]:
    """busy_s (mean over devices), per-program times (all devices) and the
    breakdown over the window: the ``bench.window`` span where the trace
    has one, else ``window_s`` from the first device or host event."""
    devices = raw["devices"]
    starts = [s for d in devices.values()
              for _, s, _ in d["ops"] + d["modules"]]
    starts += [s for _, s, _ in raw["spans"]]
    if not devices or not starts:
        return {"busy_s": 0.0, "programs": {}, "breakdown": None,
                "devices": 0, "window_s": window_s}
    if raw.get("window"):
        _, lo, d = raw["window"][0]
        hi = lo + d
    else:
        lo = min(starts)
        hi = lo + window_s * 1e9
    busy = [busy_ns(d["ops"], lo, hi) for d in devices.values()]
    modules = [e for d in devices.values() for e in d["modules"]]
    first = devices[min(devices)]
    gaps = idle_gaps(first["ops"], raw["spans"], lo, hi)
    progs = program_times(modules, lo, hi)
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "programs": progs,
            "devices": len(devices),
            "breakdown": {
                "device_ops": top({n: p["ns"] for n, p in progs.items()}),
                "idle_gaps": top(gaps)}}


def find_xplane(root: Path) -> Path:
    found = sorted(Path(root).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]
