"""Boot the FlexServe served path on one TPU chip at full width and drive it
over real HTTP.

    python chip_smoke.py

The model is ``h2o-danube-1.8b`` at its published widths (24 layers,
d_model 2560, 32/8 heads, d_ff 6912, vocab 32000, bf16) with random
weights from a fixed seed: the one supported dense configuration whose
weights fit one 16 GB TPU v5e chip whole.  The server is built by
``repro.launch.serve.boot``, the same function the launcher serves with,
and runs in this one process.  Phases:

  1. the chip: JAX must find a TPU, and Pallas kernels must compile
     rather than interpret (no fallback to the CPU);
  2. numerics: prefill plus cached decode logits at a few positions match
     one whole-sequence ``model.forward`` on the same tokens;
  3. HTTP: /healthz, /v1/infer, blocking /v1/generate, and a seeded,
     sampled stream sent twice that must come back byte-identical;
  4. /metrics: no scheduler driver errors or failed streams, one compiled
     fused decode step, and only token ids crossing to the host per tick.

Lines before the last are informational, not benchmark numbers.  The last
line of standard output is the result, ``{"ok": true, "device": {...}}``;
any failed check, error status or timeout exits non-zero without it.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "h2o-danube-1.8b"
SEED = 0
REQUEST_TIMEOUT_S = 300.0
INFER_ROWS, INFER_LEN = 3, 64
GEN_PROMPTS, GEN_LEN, GEN_NEW = 2, 48, 32
PREFIX, DECODE_STEPS = 48, 3
# Prefill + cached decode against one whole-sequence forward: the two
# paths round bf16 activations at different points through 24 layers,
# which moves logits by about a percent of their range.  A wrong cache
# position, mask or ring slot moves them by their whole range.
LOGIT_RTOL = 0.05


class SmokeFailure(RuntimeError):
    """A check of the served path failed."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _info(msg: str) -> None:
    print(f"[chip_smoke] info: {msg}", flush=True)


def _tokens(rng, n: int, length: int, vocab: int):
    return rng.integers(1, vocab, (n, length)).tolist()


def check_cached_decode(engine, *, rng) -> float:
    """Prefill PREFIX tokens, decode DECODE_STEPS more through the cache,
    and compare every produced logit row with the same positions of one
    whole-sequence forward.  Returns the worst error relative to the
    reference's largest logit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, params = engine.model, engine.params
    seq = np.asarray(_tokens(rng, 1, PREFIX + DECODE_STEPS,
                             model.config.vocab_size), np.int32)
    ref = jax.jit(model.forward)(params, {"tokens": jnp.asarray(seq)})
    ref = np.asarray(ref[0, PREFIX - 1:].astype(jnp.float32))
    logits, state = engine.prefill(
        {"tokens": jnp.asarray(seq[:, :PREFIX]),
         "lengths": jnp.asarray([PREFIX], jnp.int32)}, engine.new_state(1))
    got = [logits[0]]
    for i in range(DECODE_STEPS):
        logits, state = engine.decode(jnp.asarray(seq[:, PREFIX + i]), state)
        got.append(logits[0])
    got = np.stack([np.asarray(g.astype(jnp.float32)) for g in got])
    _check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
           "non-finite logits from prefill/decode or forward")
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    _check(err <= LOGIT_RTOL,
           f"cached decode logits differ from forward by {err:.4f} of the "
           f"logit range (tolerance {LOGIT_RTOL})")
    return err


def drive_http(app, *, rng) -> None:
    """Serve ``app`` on a free port and drive every plane over HTTP."""
    import numpy as np

    from repro.serving import FlexServeClient, FlexServeServer

    vocab = app.generation.entry_for().service.engine.model.config.vocab_size
    server = FlexServeServer(app, port=0).start(timeout=REQUEST_TIMEOUT_S)
    client = FlexServeClient(*server.address, timeout=REQUEST_TIMEOUT_S,
                             retries=0)
    try:
        def timed(label, fn):
            t0 = time.perf_counter()
            out = fn()
            _info(f"{label} {1e3 * (time.perf_counter() - t0):.1f} ms")
            return out

        health = timed("GET /healthz", client.healthz)
        _check(health.get("status") == "ready", f"/healthz said {health}")

        rows = _tokens(rng, INFER_ROWS, INFER_LEN, vocab)
        resp = timed("POST /v1/infer", lambda: client.infer({"tokens": rows}))
        logits = app.ensemble.forward({"tokens": np.asarray(rows, np.int32)})
        for i, (name, member) in enumerate(logits.items()):
            member = np.asarray(member, np.float32)
            _check(member.shape[0] == INFER_ROWS
                   and bool(np.isfinite(member).all()),
                   f"{name}: non-finite or mis-shaped logits {member.shape}")
            want = [f"class_{int(c)}" for c in member.argmax(-1)]
            _check(resp.get(f"model_{i}") == want,
                   f"/v1/infer model_{i} {resp.get(f'model_{i}')} != the "
                   f"direct forward's classes {want}")

        prompts = _tokens(rng, GEN_PROMPTS, GEN_LEN, vocab)
        out = timed("POST /v1/generate (greedy)",
                    lambda: client.generate(prompts, max_new_tokens=GEN_NEW))
        _check([len(o) for o in out["outputs"]] == [GEN_NEW] * GEN_PROMPTS
               and all(0 <= t < vocab for o in out["outputs"] for t in o),
               f"greedy generate returned {out}")

        def stream():
            events = list(client.generate_stream(
                prompts[0], max_new_tokens=GEN_NEW, temperature=0.8,
                top_p=0.95, seed=1234))
            _check(events and events[-1].get("event") == "done"
                   and events[-1].get("finish_reason") == "length",
                   f"stream ended with {events[-1:] or 'no events'}")
            return [e["token"] for e in events if e.get("event") == "token"]

        first = timed("POST /v1/generate (stream)", stream)
        second = timed("POST /v1/generate (stream, same seed)", stream)
        _check(len(first) == GEN_NEW, f"stream gave {len(first)} tokens")
        _check(json.dumps(first) == json.dumps(second),
               "seeded stream not reproducible")

        m = client.metrics()
        gen, decode = m["generate"], m["generate"]["decode"]
        driver_errors = sum(r["driver_errors"]
                            for r in m["replicas"]["per_replica"].values())
        _check(driver_errors == 0 and gen["streams"]["failed"] == 0,
               f"{driver_errors} scheduler driver errors, "
               f"{gen['streams']['failed']} failed streams")
        _check(decode["compiled_steps"] == 1,
               f"fused decode step compiled {decode['compiled_steps']} times")
        _check(decode["transfer_bytes_per_tick_p50"] == gen["num_slots"] * 4,
               f"{decode['transfer_bytes_per_tick_p50']} bytes per tick to "
               f"the host, want {gen['num_slots'] * 4} (token ids only)")
        _info(f"decode ticks {decode['ticks']}, device ms p50 "
              f"{decode['device_ms_p50']}, host ms p50 {decode['host_ms_p50']}")
    finally:
        client.close()
        server.stop()


def smoke(*, full: bool = True, require_tpu: bool = True) -> dict:
    """Run every phase; return the result line's object or raise.

    ``require_tpu=False`` skips the device checks and leaves JAX's compile
    cache alone, so the phases can be rehearsed on the CPU; ``full=False``
    serves the 2-layer smoke reduction instead of the published widths."""
    import jax
    dev = jax.devices()[0]
    if require_tpu:
        _check(dev.platform == "tpu",
               f"no TPU found: JAX's first device is {dev.platform!r}")
        from repro.kernels.common import default_interpret
        _check(not default_interpret(),
               "Pallas kernels would run in interpret mode")
        from repro.launch.serve import use_compile_cache
        _info(f"compile cache {use_compile_cache()}")
    _info(f"device {dev.platform} {dev.device_kind!r} x{len(jax.devices())}")
    import numpy as np

    from repro.launch.serve import boot

    t0 = time.perf_counter()
    app, warm_s = boot([ARCH], full=full, seed=SEED)
    _info(f"boot {time.perf_counter() - t0:.1f} s, of which decode-path warm "
          f"(compiles) {warm_s:.1f} s")
    engine = app.generation.entry_for().service.engine
    _info(f"parameter bytes "
          f"{sum(x.nbytes for x in jax.tree_util.tree_leaves(engine.params))}")
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    err = check_cached_decode(engine, rng=rng)
    _info(f"cached decode vs forward: max error {err:.5f} of the logit "
          f"range (tolerance {LOGIT_RTOL}), "
          f"{time.perf_counter() - t0:.1f} s with compiles")
    drive_http(app, rng=rng)
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        _info(f"peak_bytes_in_use {stats['peak_bytes_in_use']}")
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(jax.devices())}}


def main() -> int:
    try:
        result = smoke()
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
