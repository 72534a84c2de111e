"""Where the device's idle time goes on the host: one traced window of a
cell, split by the phase of the driver thread that covered each idle
nanosecond, beside the program's phase counters over the whole window.

    python bench/phase_split.py --workload danube.chat --seed 7 \
        --seconds 50

One process, one set-up, one window of the cell's own mix, traced as a
``--trace 1`` run traces it.  Prints one JSON line:

  split        device 0's idle seconds per phase of the cell's driver
               family (``sched`` for generate cells, ``coalesce`` for
               infer) over the traced ``bench.window``, and ``none``
               (``harness/phases.py``)
  idle_host_share   percent of that window the device sat idle while the
               driver worked (a phase that does not wait for work)
  none_share   percent of the idle time no phase covered
  counters     from ``/metrics`` read before and after the whole window:
               generate: ``tick_gap_ms`` (reap + admit + dispatch + emit +
               loop, less prefill, per tick), ``dispatch_ms``,
               ``fetch_ms``, ``tick_host_ms`` (the scheduler's own
               host_ms per tick); infer: ``infer_queue_ms`` (coalescer
               queue wait per request), ``infer_server_ms`` (residence of
               ``POST /v1/infer`` per request) and ``forward_ms``
  breakdown    the ``--trace 1`` breakdown of the same window

Not part of a benchmark run: it reads what the phase clocks record, for
PERF.md.
"""

from __future__ import annotations

import argparse
import json
import sys

import run
from harness import phases, trace

TICK_GAP = ("reap", "admit", "dispatch", "emit", "loop")


def _metrics(sess) -> dict:
    return sess.app.handle("GET", "/metrics", b"")


def counters(m0: dict, m1: dict, plane: str) -> dict:
    """Per-tick or per-request readings of the program's counters between
    two ``/metrics`` documents."""
    if plane == "generate":
        d0, d1 = m0["generate"]["decode"], m1["generate"]["decode"]
        ticks = d1["ticks"] - d0["ticks"]
        if not ticks:
            return {}
        ph0, ph1 = d0["phase_ms_total"], d1["phase_ms_total"]
        gap = sum(ph1[p] - ph0[p] for p in TICK_GAP) - 1e3 * (
            d1["prefill_s_total"] - d0["prefill_s_total"])
        return {"ticks": ticks, "tick_gap_ms": gap / ticks,
                "dispatch_ms": (ph1["dispatch"] - ph0["dispatch"]) / ticks,
                "fetch_ms": (ph1["fetch"] - ph0["fetch"]) / ticks,
                "tick_host_ms": (d1["host_ms_total"]
                                 - d0["host_ms_total"]) / ticks}
    c0, c1 = m0["coalesce"], m1["coalesce"]
    q0, q1 = c0["queue_wait_ms_hist"], c1["queue_wait_ms_hist"]
    f0, f1 = c0["forward_ms_hist"], c1["forward_ms_hist"]
    r0 = m0["routes"].get("POST /v1/infer", {"count": 0,
                                             "residence_ms_total": 0.0})
    r1 = m1["routes"]["POST /v1/infer"]
    n = q1["count"] - q0["count"]
    served = r1["count"] - r0["count"]
    return {"requests": n,
            "infer_queue_ms": (q1["sum"] - q0["sum"]) / n if n else None,
            "infer_server_ms": ((r1["residence_ms_total"]
                                 - r0["residence_ms_total"]) / served
                                if served else None),
            "forward_ms": ((f1["sum"] - f0["sum"])
                           / (f1["count"] - f0["count"])
                           if f1["count"] > f0["count"] else None)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sess = run.Session(run.Spec(), args.workload, args.seed)
    plane = sess.mix["plane"]
    m0 = _metrics(sess)
    win = sess.window(sess.mix, args.seed, args.seconds, True)
    m1 = _metrics(sess)
    tr = win["traced"]
    raw = trace.read_xplane(trace.find_xplane(tr["dir"]))
    red = trace.reduce(raw, tr["window_s"])
    out = {"workload": args.workload, "seed": args.seed,
           **(phases.reduce(raw, plane) or {}),
           "counters": counters(m0, m1, plane),
           "busy_s": red["busy_s"], "breakdown": red["breakdown"]}
    sess.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
