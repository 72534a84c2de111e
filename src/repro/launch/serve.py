"""Serving launcher: deploy one or more archs behind a FlexServe endpoint.

By default every arch is served as its REDUCED smoke variant (2 layers,
float32), which runs anywhere.  ``--full`` loads the published widths
(bf16, random weights from the seed) on the one local device; no mesh is
built and nothing is sharded.  Full-width ``h2o-danube-1.8b`` fits one
16 GB TPU v5e chip; full-width ``yi-9b`` (~8.8B parameters, ~17.6 GB of
bf16 weights) does not.

  PYTHONPATH=src python -m repro.launch.serve \
      --ensemble yi-9b yi-9b h2o-danube-1.8b --port 8000

With ``--model-store DIR`` the endpoint is store-backed: member params are
published to (or loaded from) a versioned on-disk model store with
provenance manifests, and the server exposes the lifecycle admin surface
(GET /v1/models/{name}, POST .../load /unload /rollback /gc, plus
POST /v1/engines/{name}/load|rollback for the generation engine) for hot
swaps under traffic.  /v1/generate supports token streaming
(``"stream": true``) and per-request sampling params.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from pathlib import Path

import jax

from repro.configs import ASSIGNED_ARCHS, get_config, reduce_for_smoke
from repro.core import (Ensemble, EnsembleMember, InferenceEngine,
                        ModelRegistry, SpeculativeEngine)
from repro.models.build import build_model
from repro.core.faults import FaultInjector
from repro.serving import (FlexServeApp, FlexServeServer, ModelManager,
                           ModelStore)

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and
    left alone.  Otherwise the cache lives at the fixed ``<checkout>/
    .jax_cache``, so each run of a checkout finds what the last one
    compiled.  Call it from an entry point, before the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def build_app(arch_names, *, num_classes: int = 16, max_len: int = 256,
              max_batch: int = 8, full: bool = False,
              seed: int = 0, num_slots: int = 4,
              max_queue: int = 64, generate_token_budget=None,
              default_deadline_ms=None, trace: bool = True,
              flight_recorder_size: int = 256,
              profile_dir=None, slo_config=None,
              client_weights=None, draft_model=None,
              draft_layers=None, spec_window: int = 4,
              replicas: int = 1, fault_config=None) -> FlexServeApp:
    registry = ModelRegistry()
    members = []
    engine = None
    for i, name in enumerate(arch_names):
        cfg = get_config(name)
        if not full:
            cfg = reduce_for_smoke(cfg)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(seed + i))
        reg_name = f"{name}#{i}"
        registry.register(reg_name, model, params)

        def apply(p, batch, _m=model, _c=num_classes):
            # classification readout: last-position logits over C classes
            return _m.forward(p, batch)[:, -1, :_c]

        members.append(EnsembleMember(reg_name, apply, params, num_classes))
        if engine is None and cfg.family in ("dense", "moe", "ssm",
                                             "hybrid"):
            engine = InferenceEngine(model, params, max_len=max_len,
                                     max_batch=max_batch)
    if engine is not None and draft_model is not None:
        # speculative pair: a (usually shallower) draft proposes, the
        # target verifies — seeded output stays byte-identical either way
        dcfg = get_config(draft_model)
        if not full:
            dcfg = reduce_for_smoke(dcfg)
        if draft_layers:
            dcfg = dataclasses.replace(dcfg, num_layers=int(draft_layers))
        dmodel = build_model(dcfg)
        dparams = dmodel.init(jax.random.PRNGKey(seed + 1000))
        engine = SpeculativeEngine(
            engine,
            InferenceEngine(dmodel, dparams, max_len=max_len,
                            max_batch=max_batch),
            max_window=spec_window)
        print(f"[serve] speculative decoding: draft {draft_model} "
              f"({dcfg.num_layers} layers) proposing up to "
              f"{engine.max_window} tokens/tick")
    ensemble = Ensemble(members, max_batch=max_batch)
    return FlexServeApp(registry, ensemble, engine, num_slots=num_slots,
                        max_queue=max_queue,
                        generate_token_budget=generate_token_budget,
                        default_deadline_ms=default_deadline_ms,
                        trace=trace,
                        flight_recorder_size=flight_recorder_size,
                        profile_dir=profile_dir, slo_policies=slo_config,
                        client_weights=client_weights,
                        replicas=replicas, fault_config=fault_config)


def build_store_app(arch_names, store_dir: str, *, num_classes: int = 16,
                    max_len: int = 256, max_batch: int = 8,
                    full: bool = False, seed: int = 0,
                    num_slots: int = 4, max_queue: int = 64,
                    generate_token_budget=None,
                    default_deadline_ms=None, trace: bool = True,
                    flight_recorder_size: int = 256,
                    profile_dir=None, slo_config=None,
                    client_weights=None, draft_model=None,
                    draft_layers=None, spec_window: int = 4,
                    replicas: int = 1, fault_config=None
                    ) -> FlexServeApp:
    """Store-backed startup: seed the store on first run, then serve the
    LATEST published version of every member through a ModelManager.  The
    generation engine is ALSO store-versioned: the first decode-capable
    member is loaded through the manager's engine plane, so it can be
    hot-swapped / rolled back under live streaming traffic."""
    store = ModelStore(store_dir)
    member_names = []
    engine_member = None
    for i, name in enumerate(arch_names):
        reg_name = f"{name}#{i}"
        member_names.append(reg_name)
        cfg = get_config(name)
        if not full:
            cfg = reduce_for_smoke(cfg)
        if store.latest_version(reg_name) is None:
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(seed + i))
            v = store.publish(reg_name, params, config=name,
                              source=cfg.source,
                              meta={"reduced": not full,
                                    "num_classes": num_classes,
                                    "init_seed": seed + i,
                                    "max_len": max_len,
                                    "max_batch": max_batch})
            print(f"[serve] published {reg_name} v{v} to {store_dir}")
        if engine_member is None and cfg.family in ("dense", "moe", "ssm",
                                                    "hybrid"):
            engine_member = reg_name
    # one injector shared end-to-end: checkpoint loads (manager), decode
    # drivers + replica monitor (pool), and the stream writer (handler)
    # all draw from the same deterministic schedule
    faults = FaultInjector.load(fault_config)
    manager = ModelManager(store, max_batch=max_batch, faults=faults)
    manager.bootstrap(member_names)
    app = FlexServeApp(manager=manager, num_slots=num_slots,
                       max_queue=max_queue,
                       generate_token_budget=generate_token_budget,
                       default_deadline_ms=default_deadline_ms,
                       trace=trace,
                       flight_recorder_size=flight_recorder_size,
                       profile_dir=profile_dir, slo_policies=slo_config,
                       client_weights=client_weights,
                       replicas=replicas, fault_config=faults)
    if engine_member is not None and app.generation is not None:
        draft_member = None
        if draft_model is not None:
            # publish the draft checkpoint as its own store version (its
            # manifest records the truncated depth) so the speculative
            # pair rides the normal engine lifecycle: load / canary /
            # promote / rollback move target+draft as one unit
            draft_member = f"{draft_model}#draft"
            if store.latest_version(draft_member) is None:
                dcfg = get_config(draft_model)
                if not full:
                    dcfg = reduce_for_smoke(dcfg)
                if draft_layers:
                    dcfg = dataclasses.replace(
                        dcfg, num_layers=int(draft_layers))
                dmodel = build_model(dcfg)
                dparams = dmodel.init(jax.random.PRNGKey(seed + 1000))
                v = store.publish(draft_member, dparams, config=draft_model,
                                  source=dcfg.source,
                                  meta={"reduced": not full,
                                        "num_classes": num_classes,
                                        "num_layers": dcfg.num_layers,
                                        "init_seed": seed + 1000,
                                        "max_len": max_len,
                                        "max_batch": max_batch})
                print(f"[serve] published draft {draft_member} v{v}")
        res = manager.load_engine(engine_member, draft=draft_member,
                                  max_window=spec_window)
        print(f"[serve] generation engine {res['engine']} "
              f"(alias {res['alias']})"
              + (f" + draft {res['draft']}" if res.get("draft") else ""))
    return app


def boot(arch_names, *, model_store=None, **kw):
    """Build the app the way ``main`` serves it and pre-compile its decode
    data path (fused decode step, batched-prefill buckets, slot scatter),
    so the first live streams never pay compile latency.  Store-backed
    boots skip the warm step: the manager's load_engine already warmed
    before flipping the alias.  Returns ``(app, warm seconds or None)``."""
    if model_store:
        app = build_store_app(arch_names, model_store, **kw)
    else:
        app = build_app(arch_names, **kw)
    warm_s = None
    if (app.generation is not None and app.generation.ready
            and app.manager is None):
        warm_s = app.generation.entry_for().service.warm()
        print(f"[serve] decode path warm in {warm_s:.1f}s")
    return app, warm_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ensemble", nargs="+", default=["yi-9b"],
                    choices=list(ASSIGNED_ARCHS))
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--num-classes", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--num-slots", type=int, default=4,
                    help="continuous-batching decode slots per engine")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="admission budget (rows) for the infer plane; "
                         "excess load is shed as 429 + Retry-After")
    ap.add_argument("--generate-token-budget", type=int, default=None,
                    help="generate-plane admission budget in TOKEN units "
                         "(prompt + requested max_new_tokens per request; "
                         "default 32 * max-queue)")
    ap.add_argument("--default-deadline-ms", type=float, default=None,
                    help="deadline applied to requests that don't carry "
                         "one; past-deadline requests drop as 504 before "
                         "costing a forward pass")
    ap.add_argument("--model-store", default=None, metavar="DIR",
                    help="versioned model store directory; enables the "
                         "lifecycle admin API and hot swaps")
    ap.add_argument("--no-trace", action="store_true",
                    help="disable per-request tracing + the flight "
                         "recorder (GET /v1/trace/{id} 404s)")
    ap.add_argument("--flight-recorder-size", type=int, default=256,
                    help="completed request timelines kept queryable "
                         "via GET /v1/trace/{id}")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="enable POST /v1/debug/profile; captures land "
                         "under this directory")
    ap.add_argument("--slo-config", default=None, metavar="FILE",
                    help="JSON SLO policy file ({'policies': [...]}); "
                         "enables the SLO autopilot: windowed burn-rate "
                         "evaluation with automatic canary promotion / "
                         "rollback, auditable at GET /v1/slo")
    ap.add_argument("--draft-model", default=None, metavar="ARCH",
                    choices=list(ASSIGNED_ARCHS),
                    help="enable speculative decoding: serve this arch as "
                         "the draft proposer (usually with --draft-layers "
                         "to truncate its depth); seeded outputs stay "
                         "byte-identical to non-speculative decoding, and "
                         "requests opt out per-call with "
                         "\"speculation\": false")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="truncate the draft model to this many layers "
                         "(a shallow draft is what makes proposing cheap)")
    ap.add_argument("--spec-window", type=int, default=4,
                    help="max draft tokens proposed per decode tick; the "
                         "scheduler adapts the live window to measured "
                         "acceptance")
    ap.add_argument("--replicas", type=int, default=1,
                    help="generate-plane scheduler replicas behind the "
                         "endpoint; >1 enables the health-checked replica "
                         "pool with automatic cordon/restart and "
                         "transparent stream failover (GET /v1/replicas, "
                         "POST /v1/replicas/{id}/cordon|uncordon)")
    ap.add_argument("--fault-config", default=None, metavar="FILE",
                    help="JSON fault schedule ({'faults': [...]}) for "
                         "deterministic chaos drills: inject raises/"
                         "stalls/drops at named sites (engine_step, "
                         "decode_tick, prefill, engine_install, "
                         "checkpoint_load, socket_drop, replica_kill)")
    ap.add_argument("--client-weight", action="append", default=None,
                    metavar="TAG=W",
                    help="per-client-tag fair-share weight (repeatable); "
                         "any weight enables weighted admission quotas + "
                         "weighted fair dequeue on the generate plane "
                         "(unlisted tags weigh 1.0)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)

    client_weights = None
    if args.client_weight:
        client_weights = {}
        for spec in args.client_weight:
            tag, sep, w = spec.partition("=")
            if not sep or not tag:
                ap.error(f"--client-weight needs TAG=WEIGHT, got {spec!r}")
            try:
                client_weights[tag] = float(w)
            except ValueError:
                ap.error(f"--client-weight {spec!r}: weight must be a "
                         f"number")

    kw = dict(num_classes=args.num_classes, max_len=args.max_len,
              max_batch=args.max_batch, full=args.full,
              num_slots=args.num_slots, max_queue=args.max_queue,
              generate_token_budget=args.generate_token_budget,
              default_deadline_ms=args.default_deadline_ms,
              trace=not args.no_trace,
              flight_recorder_size=args.flight_recorder_size,
              profile_dir=args.profile_dir, slo_config=args.slo_config,
              client_weights=client_weights, draft_model=args.draft_model,
              draft_layers=args.draft_layers, spec_window=args.spec_window,
              replicas=args.replicas, fault_config=args.fault_config)
    use_compile_cache()
    app, _ = boot(args.ensemble, model_store=args.model_store, **kw)
    if args.replicas > 1:
        print(f"[serve] replica pool: {args.replicas} decode replicas "
              f"(health-checked; GET /v1/replicas)")
    if args.fault_config:
        print(f"[serve] chaos: fault schedule armed from "
              f"{args.fault_config}")
    server = FlexServeServer(app, host=args.host, port=args.port)
    host, port = server.address
    print(f"[serve] FlexServe endpoint on http://{host}:{port} — "
          f"{len(app.registry)} model(s): {app.registry.names()}")
    print("[serve] routes: GET /health /healthz /metrics[?format="
          "prometheus] /v1/trace/{id} /v1/traces /v1/usage /v1/slo "
          "/v1/models /v1/models/{name} /v1/engines; POST /v1/infer "
          "/v1/detect /v1/generate (+\"stream\": true for token streaming)"
          + (" /v1/debug/profile" if args.profile_dir else "")
          + (" /v1/models/{name}/load|unload|rollback|gc "
             "/v1/engines/{name}/load|rollback"
             if app.manager else ""))
    if app.slo is not None:
        print(f"[serve] SLO autopilot: "
              f"{app.slo.stats()['policies']} policy(ies) from "
              f"{args.slo_config} — decisions audit at GET /v1/slo")
    try:
        server.httpd.serve_forever()
    except KeyboardInterrupt:
        print("\n[serve] shutting down")
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
