"""Exact split of device idle time by the host phase that covered it.

The program tiles each driver thread's time with phase spans on the
profiler's clock (``flexserve.<owner>.<phase>``, from
``repro.core.telemetry.PhaseClock``): the scheduler's driver (``sched``:
wait, reap, admit, dispatch, fetch, emit, loop) and the coalescer's
dispatcher (``coalesce``: idle, linger, assemble, forward, fetch,
scatter).  Each idle nanosecond of a device inside the window goes to the
phase of the cell's driver family that covers it, or to ``none`` where no
phase of that family does.  Unlike ``trace.idle_gaps``, which charges a
whole gap to the one span that overlaps it most, nothing is rounded to a
gap.  Where phases of one family overlap (a phase and one nested in it),
the innermost one (the latest to start) takes the time.

Works on the event tuples of ``trace.read_xplane``; a trace with no phase
spans, as from a program without phase clocks, puts all idle time in
``none``.
"""

from __future__ import annotations

import heapq
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from harness.trace import Event, union

# the driver family of each plane, and its phases that wait for work
FAMILIES = {"generate": "sched", "infer": "coalesce"}
WAITING = {"sched": ("wait",), "coalesce": ("idle", "linger")}
NONE = "none"


def idle_intervals(ops: Sequence[Event], lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no operation ran."""
    busy = union([(max(s, lo), min(s + d, hi)) for _, s, d in ops
                  if s + d > lo and s < hi])
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def flatten(spans: Sequence[Event], family: str
            ) -> List[Tuple[float, float, str]]:
    """The family's phases as sorted, non-overlapping (start, end, phase)
    segments: at each instant the innermost open phase."""
    prefix = f"flexserve.{family}."
    evs = sorted((s, s + d, n[len(prefix):]) for n, s, d in spans
                 if n.startswith(prefix) and d > 0)
    points = sorted({s for s, _, _ in evs} | {e for _, e, _ in evs})
    out: List[List] = []
    heap: List[Tuple[float, float, str]] = []   # (-start, end, phase)
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(evs) and evs[i][0] <= a:
            heapq.heappush(heap, (-evs[i][0], evs[i][1], evs[i][2]))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return [(s, e, n) for s, e, n in out]


def split(ops: Sequence[Event], spans: Sequence[Event], family: str,
          lo: float, hi: float) -> Dict[str, float]:
    """Idle nanoseconds of [lo, hi] per phase of ``family``, and ``none``
    for those no phase covers; the values sum to the idle time."""
    segs = flatten(spans, family)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in idle_intervals(ops, lo, hi):
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        covered = 0.0
        k = j
        while k < len(segs) and segs[k][0] < e:
            ov = min(e, segs[k][1]) - max(s, segs[k][0])
            if ov > 0:
                out[segs[k][2]] += ov
                covered += ov
            k += 1
        if e - s > covered:
            out[NONE] += e - s - covered
    return dict(out)


def host_ns(by_phase: Dict[str, float], family: str) -> float:
    """Idle nanoseconds while the driver worked: in a phase of its family
    that does not wait for work."""
    return sum(v for k, v in by_phase.items()
               if k != NONE and k not in WAITING[family])


def reduce(raw: Dict[str, object], plane: str) -> Optional[Dict[str, object]]:
    """Device 0's idle split over the ``bench.window`` span, for the
    driver family of ``plane``; None where the trace has no device or no
    window.  Shares are percent: ``idle_host_share`` of the window,
    ``none_share`` of the idle time."""
    devices = raw["devices"]
    if not devices or not raw.get("window"):
        return None
    family = FAMILIES[plane]
    _, lo, d = raw["window"][0]
    hi = lo + d
    by_phase = split(devices[min(devices)]["ops"], raw["spans"], family,
                     lo, hi)
    idle = sum(by_phase.values())
    return {"family": family, "window_s": d * 1e-9, "idle_s": idle * 1e-9,
            "split_s": {k: v * 1e-9 for k, v in
                        sorted(by_phase.items(), key=lambda kv: -kv[1])},
            "idle_host_share": 100.0 * host_ns(by_phase, family) / d,
            "none_share": (100.0 * by_phase.get(NONE, 0.0) / idle
                           if idle else 0.0)}
