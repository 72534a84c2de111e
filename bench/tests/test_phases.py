"""The split of device idle time by host phase, on events worked by hand
and on the small trace recorded on one TPU v5e."""

from pathlib import Path

import pytest

from harness import phases, trace

MS = 1_000_000  # ns


def test_flatten_innermost_phase_wins():
    spans = [("flexserve.sched.loop", 0, 10 * MS),
             ("flexserve.sched.reap", 2 * MS, 1 * MS),
             ("flexserve.decode_sample", 4 * MS, 1 * MS),   # not a phase
             ("flexserve.coalesce.idle", 0, 10 * MS),       # other family
             ("flexserve.sched.emit", 12 * MS, 2 * MS)]
    assert phases.flatten(spans, "sched") == [
        (0, 2 * MS, "loop"), (2 * MS, 3 * MS, "reap"),
        (3 * MS, 10 * MS, "loop"), (12 * MS, 14 * MS, "emit")]


def test_split_by_hand():
    # device busy [0,5] [9,16] [20,23] in a 30 ms window: idle [5,9],
    # [16,20], [23,30] = 15 ms
    ops = [("x", 0, 5 * MS), ("x", 9 * MS, 7 * MS), ("x", 20 * MS, 3 * MS)]
    spans = [
        ("flexserve.sched.emit", 3 * MS, 3.5 * MS),      # 5..6.5: 1.5
        ("flexserve.sched.loop", 6.5 * MS, 3.5 * MS),    # 6.5..7, 7.5..9
        ("flexserve.sched.reap", 7 * MS, 0.5 * MS),      # nested: 0.5
        ("flexserve.sched.dispatch", 16 * MS, 2 * MS),   # 16..18: 2
        ("flexserve.decode_sample", 16 * MS, 3 * MS),    # ignored
        ("PjitFunction(f)", 18 * MS, 1 * MS),            # ignored
        ("flexserve.sched.wait", 22 * MS, 9 * MS),       # 23..30: 7
    ]                                                    # 18..20: none
    got = phases.split(ops, spans, "sched", 0, 30 * MS)
    assert got == pytest.approx({"emit": 1.5 * MS, "loop": 2.0 * MS,
                                 "reap": 0.5 * MS, "dispatch": 2.0 * MS,
                                 "wait": 7.0 * MS, "none": 2.0 * MS})
    assert sum(got.values()) == pytest.approx(15 * MS)
    assert phases.host_ns(got, "sched") == pytest.approx(6.0 * MS)
    # the same window as a reduced trace
    raw = {"devices": {0: {"ops": ops, "modules": []}}, "spans": spans,
           "window": [(trace.WINDOW_SPAN, 0, 30 * MS)]}
    red = phases.reduce(raw, "generate")
    assert red["family"] == "sched"
    assert red["idle_s"] == pytest.approx(0.015)
    assert red["idle_host_share"] == pytest.approx(20.0)
    assert red["none_share"] == pytest.approx(100 * 2 / 15)
    assert list(red["split_s"])[0] == "wait"


def test_infer_family_waits_on_idle_and_linger():
    ops = [("x", 0, 2 * MS), ("x", 6 * MS, 2 * MS)]
    spans = [("flexserve.coalesce.idle", 0, 3 * MS),
             ("flexserve.coalesce.linger", 3 * MS, 2 * MS),
             ("flexserve.coalesce.assemble", 5 * MS, 0.5 * MS),
             ("flexserve.coalesce.forward", 5.5 * MS, 3 * MS),
             ("flexserve.sched.emit", 0, 10 * MS)]           # other family
    # idle [2,6] and [8,10]: forward covers 5.5..6 and 8..8.5
    got = phases.split(ops, spans, "coalesce", 0, 10 * MS)
    assert got == pytest.approx({"idle": 1 * MS, "linger": 2 * MS,
                                 "assemble": 0.5 * MS, "forward": 1 * MS,
                                 "none": 1.5 * MS})
    raw = {"devices": {0: {"ops": ops, "modules": []}}, "spans": spans,
           "window": [(trace.WINDOW_SPAN, 0, 10 * MS)]}
    assert phases.reduce(raw, "infer")["idle_host_share"] == \
        pytest.approx(15.0)


def test_no_window_or_device_no_split():
    assert phases.reduce({"devices": {}, "spans": [], "window": []},
                         "generate") is None
    assert phases.reduce({"devices": {0: {"ops": [], "modules": []}},
                          "spans": [], "window": []}, "generate") is None


def test_recorded_v5e_trace_has_no_phases():
    """The recorded trace holds engine spans only (no phase clock): every
    idle nanosecond is ``none``, and the split sums to the idle time the
    existing reduction reads (20 ms from the first program, 272883 ns
    busy; see test_trace.py)."""
    raw = trace.read_xplane(Path(__file__).parent / "data"
                            / "small_v5e.xplane.pb")
    ops = raw["devices"][0]["ops"]
    lo = min(s for _, s, _ in raw["devices"][0]["modules"])
    got = phases.split(ops, raw["spans"], "sched", lo, lo + 20 * MS)
    assert got == {"none": pytest.approx(20 * MS - 272883)}
    assert phases.reduce(raw, "generate") is None     # no bench.window


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    import checkout
    return checkout.make(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell,keys", [
    ("tiny.chat", {"ticks", "tick_gap_ms", "dispatch_ms", "fetch_ms",
                   "tick_host_ms"}),
    ("tiny.infer", {"requests", "infer_queue_ms", "infer_server_ms",
                    "forward_ms"}),
])
def test_phase_split_on_cpu(tiny, cell, keys):
    # the CPU has no device plane: no split, but the program's phase
    # counters are read over the window
    import checkout
    out = checkout.drive(tiny, (
        "import functools\n"
        "run.Session.__init__ = functools.partialmethod(\n"
        "    run.Session.__init__, require_tpu=False)\n"
        "import phase_split\n"
        f"phase_split.main(['--workload', {cell!r}, '--seed', "
        "'4294967301', '--seconds', '4'])\n"))
    assert "split_s" not in out
    assert set(out["counters"]) == keys
    assert all(v > 0 for v in out["counters"].values())
    c = out["counters"]
    if "infer_server_ms" in c:
        assert c["infer_server_ms"] > c["infer_queue_ms"]
