"""FlexServe chip benchmark: one run of one cell.

    python bench/run.py --workload danube.chat --seed 1234 --seconds 30 \
        --trace 0

One process holds the chip.  It builds the app as the launcher does
(``repro.launch.serve.build_app``, compile cache by ``use_compile_cache``),
warms only the shapes of this cell's traffic, serves it with
``FlexServeServer`` and drives it over HTTP from a load generator in a
child process that never imports JAX.  After the window it reads device
memory, stops the server, frees the program's arrays and compares a
sample of what was served with the plain float32 reference that the
configuration names.

The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the middle
of the window.  Without a TPU (or with fewer chips than the cell asks
for) it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import check, counts, results, traffic  # noqa: E402
from harness import trace as trace_mod  # noqa: E402
from harness.spec import Spec  # noqa: E402

# a traced run profiles this many seconds from the middle of the window
TRACE_SECONDS = 8.0
# weights seed: PRNGKey keeps 32 bits, members add their index
WEIGHT_SEED_MOD = 2 ** 31 - 64


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def weight_seed(seed: int) -> int:
    return seed % WEIGHT_SEED_MOD


def device_info(chips: int, require_tpu: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}


def serve_arch(cfg: Dict[str, Any]) -> str:
    """Name of the program's ModelConfig that serves ``cfg``: the
    program's own arch, or a copy registered under the configuration's
    name with ``overrides`` applied.  Every number of the file's
    ``model`` must match what the program will run.  Both may nest: a
    dict given for a field that holds a sub-config (``"moe": {...}``) is
    compared, or replaced, field by field, and only the fields it gives."""
    from repro.configs import get_config, register
    name = cfg["program_arch"]
    if cfg.get("overrides"):
        try:
            get_config(cfg["name"])
        except KeyError:
            base = get_config(name)
            register(dataclasses.replace(
                base, name=cfg["name"], **_replaced(base, cfg["overrides"])))
        name = cfg["name"]
    bad = [f"{path} is {want} in the file but {got} in the program"
           for path, want, got in _mismatches(cfg["model"],
                                              get_config(name))]
    if bad:
        raise ValueError(f"config {cfg['name']}: " + "; ".join(bad))
    return name


def _replaced(obj, overrides: Dict[str, Any]) -> Dict[str, Any]:
    """``overrides`` as ``dataclasses.replace`` arguments for ``obj``: a
    dict given for a field that holds a dataclass replaces that
    sub-config's fields and keeps the rest."""
    out = {}
    for k, v in overrides.items():
        cur = getattr(obj, k)
        if isinstance(v, dict) and dataclasses.is_dataclass(cur):
            v = dataclasses.replace(cur, **_replaced(cur, v))
        out[k] = v
    return out


def _mismatches(want: Dict[str, Any], obj, prefix: str = ""):
    """(dotted path, file's value, program's value) of every key of
    ``want`` that ``obj`` does not hold; a tuple equals the list JSON
    gives for it."""
    for k, v in want.items():
        got = getattr(obj, k, "absent")
        if isinstance(v, dict) and dataclasses.is_dataclass(got):
            yield from _mismatches(v, got, f"{prefix}{k}.")
        elif (list(got) if isinstance(got, tuple) else got) != v:
            yield f"{prefix}{k}", v, got


class Session:
    """One served app on the chip and the windows driven against it."""

    def __init__(self, spec: Spec, cell: str, seed: int, *,
                 require_tpu: bool = True):
        self.spec = spec
        self.cell = spec.cell(cell)
        self.cfg = spec.config(self.cell["config"])
        self.mix = spec.mix(self.cell["traffic"])
        self.seed = seed
        self.device = device_info(self.cell["chips"], require_tpu)
        import jax
        from repro.launch.serve import build_app, use_compile_cache
        self.jax = jax
        if require_tpu:
            log(f"compile cache {use_compile_cache()}")
        s = self.cfg["serve"]
        self.members = self.cfg["members"]
        self.wseed = weight_seed(seed)
        t = time.monotonic()
        self.app = build_app(
            [serve_arch(self.cfg)] * self.members, full=True,
            seed=self.wseed, num_classes=s["num_classes"],
            max_len=s["max_len"], max_batch=s["max_batch"],
            num_slots=s["num_slots"], max_queue=s["max_queue"],
            generate_token_budget=s["generate_token_budget"])
        log(f"built app in {time.monotonic() - t:.1f} s")
        t = time.monotonic()
        self.warm()
        log(f"warmed {self.cell['traffic']} shapes in "
            f"{time.monotonic() - t:.1f} s")
        from repro.serving import FlexServeServer
        self.server = FlexServeServer(self.app, port=0).start(timeout=60)
        self.http_warm()
        self.tmp = Path(tempfile.mkdtemp(prefix="flexserve-bench-"))

    # --- set-up --------------------------------------------------------------

    def warm(self) -> None:
        """Compile and run once every program this cell's traffic uses:
        each prefill (sequence bucket x group bucket), the first-token
        sampler, the slot scatter and the decode step; or each ensemble
        batch bucket at the mix's row length."""
        import numpy as np
        if self.mix["plane"] == "infer":
            ens = self.app.ensemble
            ens.warm({"tokens": np.ones((ens.batch_buckets.sizes[-1],
                                         self.mix["row_tokens"]),
                                        np.int32)})
            return
        svc = self.app.generation.entry_for().service
        eng = svc.engine
        lo = self.mix["prompt_tokens"]["min"]
        hi = self.mix["prompt_tokens"]["max"]
        buckets = sorted({eng.seq_buckets.bucket_for(n)
                          for n in (lo, hi)} | {
            b for b in eng.seq_buckets.sizes if lo <= b <= hi})
        groups = [g for g in eng.batch_buckets.sizes
                  if g <= min(svc.scheduler.num_slots,
                              svc.scheduler.max_prefill_batch)]
        for S in buckets:
            for g in groups:
                n = min(S, eng.max_len - 2)
                svc.submit_and_wait([[1 + i] * n for i in range(g)],
                                    max_new_tokens=2)

    def http_warm(self) -> None:
        from repro.serving import FlexServeClient
        client = FlexServeClient(*self.server.address, timeout=120,
                                 retries=0)
        try:
            if self.mix["plane"] == "infer":
                client.infer({"tokens": [[1] * self.mix["row_tokens"]]})
            else:
                list(client.generate_stream([1, 2, 3], max_new_tokens=2))
        finally:
            client.close()

    # --- one window --------------------------------------------------------

    def counters(self) -> Dict[str, float]:
        out: Dict[str, float] = {"t": time.monotonic()}
        if self.app.generation is not None:
            d = self.app.generation.entry_for().service.stats()["decode"]
            for k in ("ticks", "decode_tokens_total", "prefill_tokens_total",
                      "host_ms_total", "prefill_requests",
                      "prefill_forwards"):
                out[k] = d[k]
        if self.app.coalescer is not None:
            c = self.app.coalescer.stats()
            out["rows_total"] = c["rows_total"]
            out["batches_formed"] = c["batches_formed"]
        return out

    def window(self, mix: Dict[str, Any], seed: int, seconds: float,
               trace: bool) -> Dict[str, Any]:
        host, port = self.server.address
        spec_path = self.tmp / f"spec-{seed}.json"
        out_path = self.tmp / f"records-{seed}.json"
        spec_path.write_text(json.dumps({
            "mix": mix, "seed": seed, "seconds": seconds, "host": host,
            "port": port, "vocab": self.cfg["model"]["vocab_size"],
            "num_slots": self.cfg["serve"]["num_slots"]}))
        gen = subprocess.Popen(
            [sys.executable, str(BENCH / "harness" / "loadgen.py"),
             str(spec_path), str(out_path)],
            stdout=subprocess.PIPE, text=True)
        try:
            line = gen.stdout.readline().split()
            if not line or line[0] != "START":
                raise RuntimeError(f"load generator said {line}")
            t0 = float(line[1])
            setup_s = t0 - T_START
            traced = None
            if trace:
                trace_s = min(TRACE_SECONDS, seconds / 2)
                time.sleep(max(0.0, t0 + (seconds - trace_s) / 2
                               - time.monotonic()))
                traced = self.profile(trace_s)
            gen.wait(timeout=seconds + 300)
        finally:
            if gen.poll() is None:
                gen.kill()
                gen.wait()
        data = json.loads(out_path.read_text())
        return {"t0": data["t0"], "end": data["end"],
                "records": data["records"], "setup_s": setup_s,
                "traced": traced}

    def profile(self, trace_s: float) -> Dict[str, Any]:
        jax = self.jax
        d = self.tmp / f"trace-{time.monotonic_ns()}"
        jax.profiler.start_trace(str(d))
        # the span marks, in the trace's own clock, the stretch over which
        # the counters are read: the reduction clips every reading to it
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            c0 = self.counters()
            t_on = time.monotonic()
            time.sleep(trace_s)
            t_off = time.monotonic()
            c1 = self.counters()
        jax.profiler.stop_trace()
        log(f"trace written in {time.monotonic() - t_off:.1f} s")
        return {"dir": d, "t_on": t_on, "t_off": t_off,
                "window_s": t_off - t_on, "counters": (c0, c1)}

    # --- after the window ----------------------------------------------------

    def memory_peak(self) -> Optional[int]:
        stats = self.jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def close(self) -> None:
        """Stop serving and free every array the program holds, so the
        reference has the chip's memory."""
        self.server.stop()
        shutil.rmtree(self.tmp, ignore_errors=True)
        for a in self.jax.live_arrays():
            a.delete()
        gc.collect()

    def compare(self, seed: int, seconds: float, records, *,
                control: bool = False) -> Dict[str, Any]:
        """What was served against the reference the configuration
        names."""
        reqs = traffic.schedule(self.mix, seed, seconds)
        model = self.cfg["model"]
        ref = self.spec.reference(self.cfg["reference"])
        if self.mix["plane"] == "infer":
            return check.check_infer(
                ref, model, self.members, self.wseed, reqs,
                self.mix["row_tokens"], seed, records,
                self.cfg["serve"]["num_classes"], control=control)
        return check.check_generate(ref, model, self.wseed, reqs, seed,
                                    records, self.cfg["serve"]["max_len"],
                                    self.mix.get("sampling"),
                                    control=control)

    def judge(self, cmp: Dict[str, Any], records, *, control: bool = False
              ) -> Dict[str, Any]:
        """Each number compared beside its limit (the configuration's, for
        this plane); ``control`` judges the control's readings in the
        program's place."""
        prefix = "control_" if control else ""
        limits = self.cfg["limits"][self.mix["plane"]]
        unanswered = sum(1 for r in records
                         if "due" in r and r.get("status") == 0)
        checks = {k: {"value": cmp.get(prefix + k), "limit": v}
                  for k, v in limits.items()}
        checks["unanswered"] = {"value": unanswered, "limit": 0}
        return checks


def correct(checks: Dict[str, Any]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def per_layer(spec: Spec, sess: Session, win: Dict[str, Any]
              ) -> Dict[str, Any]:
    """Readings handed to every per-layer metric's reader."""
    tr = win["traced"]
    raw = trace_mod.read_xplane(trace_mod.find_xplane(tr["dir"]))
    red = trace_mod.reduce(raw, tr["window_s"])
    reqs = traffic.schedule(sess.mix, sess.seed, win["seconds"])
    plen = {r["i"]: r.get("prompt_len", 0) for r in reqs}
    live = (results.live_slots(win["records"], plen, tr["t_on"],
                               tr["t_off"])
            if sess.mix["plane"] == "generate" else None)
    return {"config": sess.cfg, "model": sess.cfg["model"], "mix": sess.mix,
            "members": sess.members,
            "num_slots": sess.cfg["serve"]["num_slots"],
            "peaks": counts.peaks_for(sess.device["kind"]),
            "counters": tr["counters"], "window_s": red["window_s"],
            "trace": red, "live": live}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, bench_dir: Path = BENCH,
        control: bool = False) -> Dict[str, Any]:
    """One run of one cell.  ``control`` puts the control (the reference
    in the precision below the configuration's) in the program's place
    for the comparison: such a run has to read ``correct: false``."""
    spec = Spec(bench_dir)
    sess = Session(spec, workload, seed, require_tpu=require_tpu)
    win = sess.window(sess.mix, seed, seconds, trace)
    win["seconds"] = seconds
    summary = results.summarize(win["records"], win["t0"], win["end"])
    log(f"window: {summary['attempted']} attempted, {summary['failed']} "
        f"failed, send lateness p95 {summary['send_late_p95_ms']} ms")
    log("window readings: " + json.dumps(
        {k: v for k, v in summary.items() if k.endswith(("_ms", "_s"))}))
    device = dict(sess.device, memory_peak_bytes=sess.memory_peak())
    metrics: Dict[str, Any] = {}
    breakdown = None
    if trace:
        readings = per_layer(spec, sess, win)
        red = readings["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = red["breakdown"]
        for m in spec.metrics(workload, "per_layer"):
            value = spec.reader(m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec.metrics(workload, "end_to_end"):
            value = (win["setup_s"] if m["name"] == "setup_s"
                     else summary.get(m["name"]))
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    sess.close()
    t = time.monotonic()
    cmp = sess.compare(seed, seconds, win["records"], control=control)
    log(f"reference check in {time.monotonic() - t:.1f} s: {cmp}")
    checks = sess.judge(cmp, win["records"], control=control)
    out = {"correct": correct(checks), "attempted": summary["attempted"],
           "failed": summary["failed"], "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control in the program's place (a "
                         "check of the comparison; reads correct: false)")
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  control=bool(args.control))
    except NoChip as e:
        log(f"no result: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
