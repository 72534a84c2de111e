"""Find the highest arrival rate a cell sustains: one process, one set-up,
one window per rate.

    python bench/sweep.py --workload danube.chat --seed 7 --seconds 20 \
        --rates 4,6,8,10

For each rate it prints one JSON line: requests attempted and failed,
median and p95 of the cell's latencies, and the backlog trend (median
TTFT or latency of the last quarter of the window's requests over that
of the first quarter; a queue that grows all through the window reads
well above 1).  The cell's own mix is used with ``rate_per_s`` replaced.
Not part of a benchmark run: it sets the rate a mix file then fixes.
"""

from __future__ import annotations

import argparse
import json
import sys

import run
from harness import results


def trend(records, key: str) -> float:
    done = [r for r in records if r.get("status") == 200 and r.get(key)]
    done.sort(key=lambda r: r["due"])
    q = max(1, len(done) // 4)

    def med(rs):
        return results.pctl([r[key] - r["due"] for r in rs], 0.5)

    return med(done[-q:]) / med(done[:q]) if len(done) >= 8 else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sess = run.Session(run.Spec(), args.workload, args.seed)
    key = "first" if sess.mix["plane"] == "generate" else "done"
    for k, rate in enumerate(float(x) for x in args.rates.split(",")):
        mix = dict(sess.mix, rate_per_s=rate)
        win = sess.window(mix, args.seed + k, args.seconds, False)
        s = results.summarize(win["records"], win["t0"], win["end"])
        s.pop("samples", None)
        print(json.dumps({"rate_per_s": rate, **s,
                          "backlog_trend": trend(win["records"], key)}),
              flush=True)
    sess.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
