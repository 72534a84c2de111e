"""End-to-end metrics from the load generator's records, over the whole
window.  Never imports JAX.

Every latency is measured from the time the request was due.  A request
that was refused (429, 504) or failed counts as +inf in every tail: it
missed every limit."""

from __future__ import annotations

import math
from typing import Any, Dict, List

INF = float("inf")


def pctl(values: List[float], q: float) -> float:
    """Nearest-rank percentile (an observed value; +inf counts)."""
    if not values:
        return math.nan
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


def _ok(r: Dict[str, Any]) -> bool:
    return r.get("status") == 200


def summarize(records: List[Dict[str, Any]], t0: float, end: float
              ) -> Dict[str, Any]:
    """Every end-to-end reading a cell can report, plus counts."""
    attempted = [r for r in records if "due" in r]
    failed = [r for r in attempted if not _ok(r)]
    ttft, itl, lat, late = [], [], [], []
    tokens_in_window = 0
    for r in attempted:
        late.append(r["sent"] - r["due"])
        if "times" in r:                         # a generate stream
            if _ok(r) and r["times"]:
                ttft.append(1e3 * (r["first"] - r["due"]))
                ts = r["times"]
                itl.extend(1e3 * (b - a) for a, b in zip(ts, ts[1:]))
                tokens_in_window += sum(t0 <= t < end for t in ts)
            else:
                ttft.append(INF)
                itl.append(INF)
        else:                                    # an infer request
            lat.append(1e3 * (r["done"] - r["due"]) if _ok(r) else INF)
    window = end - t0
    out = {"attempted": len(attempted), "failed": len(failed),
           "send_late_p95_ms": 1e3 * pctl(late, 0.95) if late else None,
           "samples": {"ttft": len(ttft), "itl": len(itl),
                       "infer": len(lat)}}
    if ttft:
        out["ttft_p95_ms"] = pctl(ttft, 0.95)
        out["ttft_p50_ms"] = pctl(ttft, 0.50)
        out["itl_p95_ms"] = pctl(itl, 0.95)
        out["itl_p50_ms"] = pctl(itl, 0.50)
        out["gen_tokens_per_s"] = tokens_in_window / window
    if lat:
        out["infer_p95_ms"] = pctl(lat, 0.95)
        out["infer_p50_ms"] = pctl(lat, 0.50)
    return out


def live_slots(records: List[Dict[str, Any]], prompt_len: Dict[int, int],
               lo: float, hi: float, step: float = 0.005
               ) -> Dict[str, float]:
    """Time-average over [lo, hi] of the streams holding a decode slot
    (first token received, last not yet) and of the sum of their contexts
    (prompt plus tokens received so far)."""
    n_steps = max(1, int((hi - lo) / step))
    active = [0.0] * n_steps
    ctx = [0.0] * n_steps
    for r in records:
        ts = r.get("times") or []
        if not ts or not _ok(r):
            continue
        p = prompt_len[r["i"]]
        for j, (a, b) in enumerate(zip(ts, ts[1:] + [r["done"]])):
            k0 = max(0, int((a - lo) / step))
            k1 = min(n_steps, int((b - lo) / step))
            for k in range(k0, k1):
                active[k] += 1
                ctx[k] += p + j + 1
    return {"active": sum(active) / n_steps, "context": sum(ctx) / n_steps}
