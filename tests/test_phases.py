"""Phase clocks: the spans and counters that tile the scheduler's driver,
the coalescer's dispatcher and the HTTP handlers, on the profiler's
clock (``repro.core.telemetry.PhaseClock``)."""

import concurrent.futures
import glob
import threading
import time

import jax
import numpy as np
import pytest

from conftest import smoke_model
from repro.core import Ensemble, EnsembleMember, InferenceEngine
from repro.core.batching import BucketSpec
from repro.core.scheduler import (SCHED_PHASES, ContinuousBatchingScheduler,
                                  SchedulerService)
from repro.core.telemetry import PhaseClock
from repro.serving import (BatchCoalescer, FlexServeApp, FlexServeClient,
                           FlexServeServer)
from repro.serving.coalesce import COALESCE_PHASES


class RecordingSpan:
    """Stands in for ``jax.profiler.TraceAnnotation``: records every
    span's begin (name, metadata) and end in order."""

    log = []

    def __init__(self, name, **meta):
        self.name = name
        self.meta = meta

    def __enter__(self):
        RecordingSpan.log.append(("begin", self.name, self.meta))
        return self

    def __exit__(self, *exc):
        RecordingSpan.log.append(("end", self.name))


@pytest.fixture
def recorded(monkeypatch):
    RecordingSpan.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", RecordingSpan)
    return RecordingSpan.log


def test_phase_counters_and_nesting(recorded):
    clock = PhaseClock("own", ("a", "b", "c"))
    t0 = time.perf_counter()
    with clock.phase("a", tick=7):
        time.sleep(0.02)
        with clock.phase("b"):          # suspends a
            time.sleep(0.03)
        time.sleep(0.01)
    wall_ms = 1e3 * (time.perf_counter() - t0)
    assert recorded == [
        ("begin", "flexserve.own.a", {"tick": 7}),
        ("end", "flexserve.own.a"),
        ("begin", "flexserve.own.b", {}),
        ("end", "flexserve.own.b"),
        ("begin", "flexserve.own.a", {"tick": 7}),   # a resumes
        ("end", "flexserve.own.a")]
    st = clock.stats()
    ms = st["phase_ms_total"]
    assert st["phase_count"] == {"a": 1, "b": 1, "c": 0}
    assert ms["c"] == 0.0
    assert 30.0 <= ms["a"] < 45.0       # its own time, b's left out
    assert 30.0 <= ms["b"] < 45.0
    assert ms["a"] + ms["b"] == pytest.approx(wall_ms, abs=1.0)
    with pytest.raises(KeyError):
        clock.phase("nope")


def test_phases_of_other_threads_do_not_suspend(recorded):
    clock = PhaseClock("own", ("a", "b"))
    inside = threading.Event()
    release = threading.Event()

    def other():
        with clock.phase("b"):
            inside.set()
            release.wait(5)

    th = threading.Thread(target=other)
    with clock.phase("a"):
        th.start()
        assert inside.wait(5)
        time.sleep(0.02)
        release.set()
        th.join(5)
    assert not th.is_alive()
    begins = [e[1] for e in recorded if e[0] == "begin"]
    assert begins == ["flexserve.own.a", "flexserve.own.b"]
    st = clock.stats()["phase_ms_total"]
    assert st["a"] >= 20.0 and st["b"] >= 20.0


def test_tick_phases_and_engine_span_names(recorded):
    cfg, model, params = smoke_model("yi-9b")
    engine = InferenceEngine(model, params, max_len=64, max_batch=4)
    sched = ContinuousBatchingScheduler(engine, num_slots=2)
    sched.submit([1, 2, 3], max_new_tokens=3)
    while not sched.idle():
        sched.step()
    begins = [e[1] for e in recorded if e[0] == "begin"]
    # the engine keeps its span names, nested inside the tick's phases
    assert begins[:8] == [
        "flexserve.sched.reap", "flexserve.sched.admit",
        "flexserve.prefill", "flexserve.sample", "flexserve.insert_rows",
        "flexserve.sched.dispatch", "flexserve.decode_sample",
        "flexserve.sched.fetch"]
    ticks = [e[2]["tick"] for e in recorded
             if e[0] == "begin" and e[1] == "flexserve.sched.emit"]
    assert ticks == list(range(len(ticks))) and len(ticks) == 2
    st = sched.phases.stats()
    assert st["phase_count"]["reap"] == sched.steps
    for p in ("dispatch", "fetch", "emit"):
        assert st["phase_count"][p] == sched.decode_ticks == 2
    assert st["phase_count"]["wait"] == st["phase_count"]["loop"] == 0


def test_service_stats_report_phases():
    cfg, model, params = smoke_model("yi-9b")
    engine = InferenceEngine(model, params, max_len=64, max_batch=4)
    svc = SchedulerService(engine, num_slots=2)
    try:
        svc.submit_and_wait([[1, 2, 3], [4, 5]], max_new_tokens=4,
                            timeout=120)
        d = svc.stats()["decode"]
    finally:
        svc.close()
    assert set(d["phase_ms_total"]) == set(SCHED_PHASES)
    assert d["phase_count"]["dispatch"] == d["ticks"] > 0
    assert d["dispatch_ms"] == pytest.approx(
        d["phase_ms_total"]["dispatch"] / d["ticks"])
    assert d["fetch_ms"] > 0.0
    assert d["phase_count"]["loop"] >= d["ticks"]


def _xplane_events(root):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{root}/plugins/profile/*/*.xplane.pb"))[-1]
    pd = ProfileData.from_file(path)
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in ln.events]
            for plane in pd.planes if plane.name.startswith("/host:")
            for ln in plane.lines]


def test_capture_sched_phases_tile_driver_thread(tmp_path):
    """A real capture on the CPU: from the first tick to the last, the
    ``flexserve.sched.*`` spans cover the driver thread's time."""
    cfg, model, params = smoke_model("yi-9b")
    engine = InferenceEngine(model, params, max_len=64, max_batch=4)
    svc = SchedulerService(engine, num_slots=2)
    try:
        svc.submit_and_wait([[1, 2, 3], [4, 5]], max_new_tokens=3,
                            timeout=120)           # compile first
        jax.profiler.start_trace(str(tmp_path))
        try:
            svc.submit_and_wait([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=6,
                                timeout=120)
        finally:
            jax.profiler.stop_trace()
    finally:
        svc.close()
    lines = [ln for ln in _xplane_events(tmp_path)
             if any(n == "flexserve.sched.reap" for n, _, _ in ln)]
    assert len(lines) == 1                         # one driver thread
    sched = sorted((s, e) for n, s, e in lines[0]
                   if n.startswith("flexserve.sched."))
    lo = min(s for n, s, _ in lines[0] if n == "flexserve.sched.reap")
    hi = max(e for n, _, e in lines[0] if n == "flexserve.sched.emit")
    covered, t = 0, lo
    for s, e in sched:
        s, e = max(s, t), min(e, hi)
        if e > s:
            covered += e - s
            t = e
    assert hi > lo
    assert covered >= 0.95 * (hi - lo), (covered, hi - lo)


class SleepForward:
    def __init__(self, delay_s):
        self.delay_s = delay_s

    def __call__(self, batch):
        time.sleep(self.delay_s)
        return {"y": batch["x"] * 2.0}


def test_coalescer_phases_tile_its_thread():
    t0 = time.perf_counter()
    co = BatchCoalescer(SleepForward(0.005), BucketSpec.pow2(8),
                        max_wait_ms=2.0)
    try:
        batches = [{"x": np.full((1 + i % 3, 4), i, np.float32)}
                   for i in range(24)]

        def send(b):
            time.sleep(0.01 * float(b["x"][0, 0] % 5))
            return co.submit(b)

        with concurrent.futures.ThreadPoolExecutor(4) as ex:
            outs = list(ex.map(send, batches))
        for b, out in zip(batches, outs):
            np.testing.assert_array_equal(out["y"], b["x"] * 2.0)
        time.sleep(0.05)                    # some idle time too
    finally:
        co.close()
    wall_ms = 1e3 * (time.perf_counter() - t0)
    st = co.stats()
    assert set(st["phase_ms_total"]) == set(COALESCE_PHASES)
    total = sum(st["phase_ms_total"].values())
    assert total == pytest.approx(wall_ms, rel=0.05)
    n = st["batches_formed"]
    assert st["phase_count"]["forward"] == st["phase_count"]["fetch"] == n
    assert st["phase_ms_total"]["forward"] >= 5.0 * n * 0.9
    assert st["phase_ms_total"]["idle"] > 0.0


def _ensemble_app():
    cfg, model, params = smoke_model("yi-9b")
    members = []
    for i in range(2):
        pp = model.init(jax.random.PRNGKey(i))
        members.append(EnsembleMember(
            f"m{i}", lambda p, b, _m=model: _m.forward(p, b)[:, -1, :4],
            pp, 4))
    return FlexServeApp(ensemble=Ensemble(members, max_batch=4))


def test_residence_covers_handle_time():
    app = _ensemble_app()
    srv = FlexServeServer(app).start()
    client = FlexServeClient(*srv.address, retries=0)
    try:
        client.infer({"tokens": [[1, 2, 3, 4]]})
        deadline = time.monotonic() + 5
        while True:            # the handler records after its last write
            route = app._metrics()["routes"]["POST /v1/infer"]
            if route["residence_ms_total"] > 0 or \
                    time.monotonic() > deadline:
                break
            time.sleep(0.01)
        http = app._metrics()["http"]
    finally:
        client.close()
        srv.stop()
    assert route["count"] == 1
    assert route["residence_ms_total"] >= route["mean_ms"] > 0
    for p in ("read", "handle", "write"):
        assert http["phase_count"][p] >= 1
    # respond: the ensemble's vote in the route and the JSON encoding
    assert http["phase_count"]["respond"] >= 2
