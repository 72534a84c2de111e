"""The reduction from trace to metrics, checked on events worked by hand
and on a small trace recorded on one TPU v5e."""

from pathlib import Path

import pytest

from harness import trace

MS = 1_000_000  # ns


def test_union_and_busy():
    ops = [("a", 0, 4 * MS), ("b", 2 * MS, 4 * MS), ("c", 10 * MS, 2 * MS)]
    assert trace.union([(0, 4), (2, 6), (10, 12)]) == [(0, 6), (10, 12)]
    # busy: [0, 6] and [10, 12] = 8 ms; clipped to [1, 11] = 5 + 1 ms
    assert trace.busy_ns(ops, 0, 20 * MS) == 8 * MS
    assert trace.busy_ns(ops, 1 * MS, 11 * MS) == 6 * MS


def test_program_times_strip_module_ids():
    mods = [("jit_decode_and_sample(123)", 0, 5 * MS),
            ("jit_decode_and_sample(123)", 9 * MS, 7 * MS),
            ("jit__unknown(77)", 20 * MS, 3 * MS)]
    got = trace.program_times(mods)
    assert got == {"jit_decode_and_sample": {"count": 2, "ns": 12 * MS},
                   "jit__unknown": {"count": 1, "ns": 3 * MS}}


def test_idle_gaps_by_host_span():
    # device busy [0,5] [9,16] [20,23] in a 30 ms window: gaps [5,9]
    # (4 ms), [16,20] (4 ms), [23,30] (7 ms)
    ops = [("x", 0, 5 * MS), ("x", 9 * MS, 7 * MS), ("x", 20 * MS, 3 * MS)]
    spans = [("flexserve.prefill", 4 * MS, 4 * MS),       # covers 3 ms of gap 1
             ("PjitFunction(f)", 5 * MS, 3 * MS),         # ties it: loses
             ("flexserve.decode_sample", 17 * MS, 1 * MS)]  # 1 ms of gap 2
    got = trace.idle_gaps(ops, spans, 0, 30 * MS)
    assert got == {"flexserve.prefill": 4 * MS,
                   "flexserve.decode_sample": 4 * MS,
                   "host: no span": 7 * MS}


def test_reduce_breakdown():
    raw = {"devices": {0: {"ops": [("x", 0, 5 * MS), ("x", 9 * MS, 7 * MS)],
                           "modules": [("jit_a(1)", 0, 5 * MS),
                                       ("jit_b(2)", 9 * MS, 7 * MS)]}},
           "spans": [("flexserve.prefill", 6 * MS, 2 * MS)]}
    got = trace.reduce(raw, 0.020)
    assert got["busy_s"] == pytest.approx(0.012)
    assert got["breakdown"]["device_ops"] == [["jit_b", 0.007],
                                              ["jit_a", 0.005]]
    assert got["breakdown"]["idle_gaps"] == [["flexserve.prefill", 0.004],
                                             ["host: no span", 0.004]]


def test_recorded_v5e_trace():
    """Three runs of one jitted bf16 2048x2048 matmul, each inside a
    ``flexserve.decode_sample`` annotation, traced on one TPU v5e.  Worked
    by hand from its events (ns):

      program jit__lambda: 91012 + 90942 + 90945 = 272899, 3 runs
      ops of run 1: [..943, ..956] [..956, ..959] [..960, ..9951]
                    -> 16 + 90991 = 91007
      run 2: 13 + 3 + 90921 = 90937 (1 ns gaps between them)
      run 3: 13 + (3 + 90923 merged) = 90939
      busy = 91007 + 90937 + 90939 = 272883
      idle over 20 ms from the first program (the file has no
      bench.window span): 20e6 - 272883 = 19727117, of which 3 ns come
      before the first op and 5 ns lie between ops of one run (no host
      span), and the rest under the decode_sample spans.

    The device lines run about 1.2 ms ahead of the host spans in this
    file: the two clocks are aligned only to that, so idle gaps are
    charged to host spans to within a millisecond or two."""
    raw = trace.read_xplane(Path(__file__).parent / "data"
                            / "small_v5e.xplane.pb")
    assert list(raw["devices"]) == [0]
    got = trace.reduce(raw, 0.020)
    assert got["programs"] == {"jit__lambda": {"count": 3, "ns": 272899.0}}
    assert got["busy_s"] == pytest.approx(272883e-9, abs=1e-12)
    gaps = dict(got["breakdown"]["idle_gaps"])
    assert gaps["host: no span"] == pytest.approx(8e-9, abs=1e-12)
    assert gaps["flexserve.decode_sample"] == pytest.approx(19727109e-9,
                                                            abs=1e-12)
    assert got["window_s"] == pytest.approx(0.020)


def test_window_span_clips_every_reading():
    # window span [10, 30] ms: run 1 starts before it and is left out of
    # the programs; busy counts only [10, 30]
    raw = {"devices": {0: {"ops": [("x", 0, 15 * MS), ("x", 20 * MS, 5 * MS),
                                   ("x", 32 * MS, 3 * MS)],
                           "modules": [("jit_a(1)", 0, 15 * MS),
                                       ("jit_a(1)", 20 * MS, 5 * MS),
                                       ("jit_a(1)", 32 * MS, 3 * MS)]}},
           "spans": [], "window": [(trace.WINDOW_SPAN, 10 * MS, 20 * MS)]}
    got = trace.reduce(raw, 99.0)
    assert got["window_s"] == pytest.approx(0.020)
    assert got["busy_s"] == pytest.approx(0.010)
    assert got["programs"] == {"jit_a": {"count": 1, "ns": 5 * MS}}
