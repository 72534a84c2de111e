"""Readings that set a cell's correctness limit: the program's gap on many
seeds and the control's (every linear layer in float8) on the same
prompts.

    python bench/calibrate.py --workload danube.chat --seconds 12 \
        --seeds 101,102,103

One process, one set-up: a short window at the cell's own load per seed,
then the program's arrays are freed and the reference runs over each
window's sample with the control beside it.  Prints one JSON line per
seed with the program's readings and the control's (``control_``), and
whether a run would call each correct against the cell's limits
(``correct``, ``control_correct``).  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    seeds = [int(x) for x in args.seeds.split(",")]
    sess = run.Session(run.Spec(), args.workload, seeds[0])
    windows = []
    for seed in seeds:
        win = sess.window(sess.mix, seed, args.seconds, False)
        windows.append((seed, win["records"]))
    sess.close()
    for seed, records in windows:
        got = sess.compare(seed, args.seconds, records, control=True)
        verdict = {name: run.correct(sess.judge(got, records,
                                                control=control))
                   for name, control in (("correct", False),
                                         ("control_correct", True))}
        print(json.dumps({"seed": seed, **got, **verdict}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
