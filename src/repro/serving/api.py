"""REST API schema (kept byte-compatible with the paper's response format).

POST /v1/infer     {"inputs": {"tokens": [[...], ...]}, "policy": "soft_vote",
                    "target": "canary"?}
    -> {"model_0": ["class_a", ...], "model_1": [...], "ensemble": [...],
        "policy": "soft_vote"}                                  (paper §2.3)

POST /v1/detect    {"inputs": {...}, "positive_class": 3, "policy": "or",
                    "threshold": 0.5, "target": "stable"?}
    -> {"model_0": [true, false, ...], ..., "ensemble": [...]}   (paper §2.1)

``target`` (optional) names a version alias maintained by the lifecycle
manager; requests without one hit the default ("stable") alias.

Request plane (every inference route; all fields optional):

    "priority":    "interactive" (default) | "bulk".  Bulk may only
                   occupy a fraction of each queue's budget, so under
                   overload bulk sheds first (cheapest-first rejection)
                   and interactive admissions overtake a bulk backlog
                   (weighted dequeue).  Budgets are charged in ROWS on
                   the infer plane and in TOKENS (prompt length +
                   requested max_new_tokens) on the generate plane, so a
                   single huge generation can't slip in as "one row".
    "deadline_ms": per-request latency budget from arrival.  A request
                   past its deadline is dropped at the next hand-off
                   (before it costs a forward pass) -> 504.
    "client":      free-form client tag (observability).
    "trace_id":    request id echoed in stream terminals (default
                   server-generated).

    The same facts travel as headers when a body field is awkward:
    ``X-FlexServe-Priority``, ``X-FlexServe-Deadline-Ms``,
    ``X-FlexServe-Client``, ``X-Request-Id`` (body wins).

    Every non-2xx response body is the structured error taxonomy:
        {"error": {"code": "queue_full"|"client_quota"|"bad_request"|
                           "not_found"|"conflict"|"unavailable"|
                           "deadline_exceeded"|"internal"|...,
                   "message": str, "retryable": bool,
                   "trace_id": str|null}}
    Clients dispatch typed errors off ``code`` and retry ONLY when
    ``retryable`` is true.  Overload responses: 429 code "queue_full"
    (or "client_quota") with a ``Retry-After`` seconds header (may be
    fractional) when a queue's budget is full; 504 code
    "deadline_exceeded" on a missed deadline.

POST /v1/generate  {"prompts": [[1,2,3], ...], "max_new_tokens": 16,
                    "temperature"?: 0.8, "top_k"?: 40, "top_p"?: 0.95,
                    "seed"?: 7, "stop"?: [50256], "eos_id"?: 2,
                    "speculation"?: true, "stream"?: false,
                    "target"?: "canary"}
    -> {"outputs": [[...], ...], "steps": n, "prompt_lengths": [...],
        "finish_reasons": ["length"|"eos"|"stop", ...]}

    ``speculation`` (default true) opts a request out of speculative
    decoding when false; it is a no-op on a non-speculative engine.
    Seeded outputs are byte-identical either way — speculation changes
    latency, never tokens.

    With ``"stream": true`` (exactly ONE prompt) the response is chunked
    transfer encoding, application/x-ndjson — one JSON event per chunk:
        {"event": "token", "token": t, "index": i}          per token
        {"event": "done", "tokens": [...], "finish_reason": ...,
         "token_count": n, "prompt_length": l, "ttft_ms": ...,
         "total_ms": ..., "engine": "name@vN", "sampling": {...},
         "speculation": {"proposed": p, "accepted": a,
                         "acceptance_rate": a/p}}
    (or a terminal {"event": "error", "error": ...}).  The terminal
    ``speculation`` summary is zeros on a non-speculative engine or an
    opted-out request.  Disconnecting mid-stream cancels the request and
    frees its decode slot.

GET  /v1/models    -> {"models": [{name, version, arch, family, params,
                                   source, param_hash?}, ...]}

Lifecycle admin surface (when a ModelManager backs the endpoint):

GET  /v1/models/{name}          -> {"versions": [manifest, ...],
                                    "loaded_versions": [...],
                                    "active": {alias: version},
                                    "previous": {alias: version},
                                    "traffic": {"name@vN": {batches, rows}}}
POST /v1/models/{name}/load     {"version"?: n, "alias"?: "canary",
                                 "warm"?: true}
POST /v1/models/{name}/unload   {"version"?: n}   (omit -> whole member)
POST /v1/models/{name}/rollback {"alias"?: "stable"}
POST /v1/models/{name}/gc       {"keep_last_n": 3}
    -> {"deleted": [...], "kept": [...], "protected": [...]}
    (retention GC: never deletes a version referenced by a serving alias)

Generation-engine lifecycle (versioned engines under the same manager):

GET  /v1/engines                -> {"aliases": {alias: "name@vN"},
                                    "ready": true}
POST /v1/engines/{name}/load     {"version"?: n, "alias"?: "canary"}
POST /v1/engines/{name}/rollback {"alias"?: "stable"}
    Hot-swaps the alias's engine under live decode traffic; in-flight
    streams drain on the old engine.  /v1/generate targets an engine
    alias per request via "target".

Replica pool surface (with ``--replicas N``; see repro.serving.replica):

GET  /v1/replicas  -> {"replicas": {enabled, count, ready, warming,
                       degraded, cordoned, restarting, cordoned_ids,
                       restarts, kills, cordons, failovers,
                       failover_failures, evacuations,
                       per_replica: {id: {state, restarts, active,
                                          pending, driver_errors, ...}}}}
POST /v1/replicas/{id}/cordon    -> {"replica": {...}}
    Drain-aware operator cordon: the replica takes no new work, its
    in-flight requests finish in place.  404 unknown id; 409 without a
    replica pool (single-service mode).
POST /v1/replicas/{id}/uncordon  -> {"replica": {...}}
    Returns the replica to ready (restarting its service first if it
    was auto-killed).

GET  /health       -> {"status": "ok"}            (liveness: process is up)
GET  /healthz      -> 200 {"status": "ready", "replicas": {...}}
                      | 503 {"error": ...}
                      (readiness: >=1 loaded model, coalescer alive, not
                       shutting down, AND >=1 generation replica ready —
                       the payload aggregates per-replica health: ready
                       count + cordoned list — so external LBs stop
                       routing to a dead pool)
GET  /metrics      -> {"uptime_s", "requests",
                       "routes": {"<METHOD> <path>": {count, mean_ms,
                                  max_ms, residence_ms_total}},
                       "http": {phase_ms_total, phase_count},
                       "coalesce": {batches_formed, rows_total,
                                    mean_rows_per_batch, max_rows_per_batch,
                                    queue_wait_p50_ms, queue_wait_p95_ms,
                                    queue_wait_ms_hist, forward_ms_hist,
                                    adaptive_linger, effective_linger_ms,
                                    ewma_interarrival_ms,
                                    phase_ms_total, phase_count},
                       "ensemble_compiles": {"<bucket>": count, ...},
                       "admission": {max_queue, bulk_max,
                                     default_deadline_ms,
                                     planes: {plane: {depth, depth_total,
                                              budget, high_water, admitted,
                                              shed, deadline_miss,
                                              ewma_release_gap_ms}}},
                       "generate": {steps, active_slots, pending, num_slots,
                                    completed, cancelled, deadline_missed,
                                    request_latency_p50_ms/…_p95_ms,
                                    ttft_p50_ms/…_p95_ms,
                                    inter_token_p50_ms/…_p95_ms,
                                    request_latency_ms_hist, ttft_ms_hist,
                                    inter_token_ms_hist, queue_wait_ms_hist,
                                    decode: {device_sampling, ticks,
                                             host_ms_p50/p95,
                                             device_ms_p50/p95,
                                             prefill_ms_p50,
                                             transfer_bytes_per_tick_p50,
                                             transfer_bytes_total,
                                             prefill_forwards,
                                             prefill_requests,
                                             prefill_s_total,
                                             device_ms_total,
                                             host_ms_total,
                                             phase_ms_total, phase_count,
                                             dispatch_ms, fetch_ms,
                                             compiled_steps,
                                             host_ms_hist, device_ms_hist,
                                             prefill_ms_hist,
                                             transfer_bytes_hist},
                                    pager: {page_size, pages_total,
                                            pages_used, pages_free,
                                            pages_used_high_water,
                                            page_utilization, oom_events,
                                            prefix_* , preempt_recompute,
                                            resumes_without_recompute,
                                            prefill_tokens_forwarded,
                                            prefill_tokens_reused}
                                           (zeroed for dense engines),
                                    speculation: {enabled, max_window,
                                                  window, acceptance_ema,
                                                  spec_ticks,
                                                  proposed_tokens,
                                                  accepted_tokens,
                                                  acceptance_rate, k_hist,
                                                  draft_ms_total,
                                                  verify_ms_total,
                                                  draft_share_estimate}
                                           (zeroed for non-speculative
                                            engines),
                                    streams: {started, completed,
                                              cancelled, failed,
                                              deadline, paused},
                                    engines: {alias: {...}}},
                       "lifecycle": {loads, unloads, swaps, rollbacks, ...}
                                    (zeroed without a ModelManager),
                       "usage": {clients, versions, requests, errors,
                                 prefill_tokens, decode_tokens, device_ms,
                                 decode_device_ms, decode_host_ms,
                                 prefill_ms, transfer_bytes}
                                (cost-attribution totals; zeroed at boot),
                       "slo": {policies, evaluations, decisions,
                               promotions, rollbacks, breaches}
                              (zeroed without an SLO config),
                       "replicas": {enabled, count, ready, degraded,
                                    cordoned, restarts, kills, failovers,
                                    evacuations, per_replica: {...}}
                                   (zeroed without a replica pool),
                       "faults": {enabled, specs, fired_total,
                                  sites: {site: {specs, hits, fired}}}
                                 (zeroed without --fault-config),
                       "telemetry": {capacity, in_flight, completed,
                                     completed_total, leaked_total}}

    A route's ``mean_ms``/``max_ms`` time ``FlexServeApp.handle``;
    ``residence_ms_total`` sums each request's time in the server, from
    its first byte read to its last byte written (a stream's last chunk).

    ``phase_ms_total``/``phase_count`` are lifetime host milliseconds and
    counts of the phases that tile a thread's time, read as differences
    (``repro.core.telemetry.PhaseClock``; every phase is also a
    ``flexserve.<owner>.<phase>`` span in a profiler capture):
    ``http`` read / handle / respond / write over every handler thread;
    ``coalesce`` idle / linger / assemble / forward / fetch / scatter on
    the dispatch thread; ``decode`` wait / reap / admit / dispatch / fetch
    / emit / loop on the scheduler's driver.  ``dispatch_ms`` and
    ``fetch_ms`` are the mean per tick.  ``device_ms_*`` is host-clock
    time from the decode dispatch to the fetched ids (dispatch plus
    fetch), not device time: a profiler capture gives the device's.

    ``*_hist`` values are fixed-bucket histogram snapshots:
    {"le": [bounds..., "+Inf"], "counts": [cumulative...], "count", "sum",
     "exemplar"?: {"trace_id", "value"}} — the exemplar names the slowest
    observed request so dashboards can link a tail spike to its trace.

GET  /metrics?format=prometheus
    -> text/plain; version=0.0.4 Prometheus exposition of the same
    document: nested keys flatten to ``flexserve_<section>_<key>`` gauges
    and every ``*_hist`` renders as a histogram family
    (``flexserve..._bucket{le="..."}`` / ``_sum`` / ``_count``), with the
    exemplar trace id as an ``# EXEMPLAR`` comment line.

Telemetry surface (the span tracer keyed by ``trace_id``):

GET  /v1/trace/{trace_id}
    -> {"trace_id", "plane", "client", "priority", "in_flight",
        "started_unix", "duration_ms", "status", "finish_reason",
        "error",
        "spans":  [{"name", "start_ms", "end_ms", "duration_ms",
                    "attrs"?}, ...],     # http_parse, queue_wait,
                                         # coalesce_queue, coalesce_forward,
                                         # prefill
        "events": [{"name", "t_ms", "attrs"?}, ...],
                                         # admitted, shed, deadline_drop,
                                         # scheduler_queued, first_token,
                                         # preempt, resume, reattach,
                                         # request_finished
        "counters": {...}}               # decode_ticks, decode_device_ms,
                                         # decode_host_ms,
                                         # decode_transfer_bytes,
                                         # stream_events, stream_stalls,
                                         # swap_drain_forced
    404 when the id is neither in flight nor in the flight recorder's
    ring of recently completed requests (or tracing is disabled).
    Every response from a traced plane carries its ``X-Request-Id``
    header; shed (429) and deadline (504) requests leave timelines too.

GET  /v1/traces  -> {"in_flight": [...ids], "recent": [{trace_id, plane,
                     client, status, finish_reason, duration_ms,
                     "version"?}, ...],
                     "telemetry": {capacity, in_flight, completed, ...}}
    Query filters (combinable): ``?status=504`` (exact HTTP status),
    ``?client=tenant-a`` (exact client tag), ``?min_duration_ms=250``
    (at-least duration), ``?limit=50`` (max rows, default 20).  With a
    filter active the whole completed ring is scanned before the limit
    applies; 400 on malformed values.

SLO autopilot & cost accounting (PR 8; see repro.core.slo):

GET  /v1/usage   -> {"clients": {tag: usage}, "versions": {label: usage},
                     "totals": usage}
    where usage = {requests, errors, prefill_tokens, decode_tokens,
                   device_ms, decode_device_ms, decode_host_ms,
                   prefill_ms, transfer_bytes,
                   "planes": {plane: {requests, device_ms, tokens}}}.
    Per-client / per-version cost attribution rolled up from the
    scheduler's per-request O(1) cost counters at trace-seal time
    (device_ms = decode share + prefill share).  Untagged requests land
    under "_untagged", engine-less planes under "_unversioned".
    Query filters: ``?client=tag`` / ``?version=label`` narrow the
    corresponding table to one key.

GET  /v1/slo     -> {"enabled", policies (count), evaluations, decisions
                     (count or list), promotions, rollbacks, breaches,
                     "policies": [{...policy fields, "eval": {state:
                        "observing"|"healthy"|"breach"|"no_target"|
                        "no_traffic", engine, fast/slow: {sli, burn_rate,
                        failed}}}, ...],
                     "decisions": [{seq, trace_id, unix_time, policy,
                        action: "promote"|"rollback", alias, engine,
                        stable_engine, error, fast_burn, slow_burn,
                        failed_objectives, window_count, result}, ...],
                     "sli": {plane|client|version: {name: {count,
                        error_rate, deadline_miss_rate, p50_ms, p95_ms,
                        p99_ms, ttft_p95_ms, ...}}}}
    ``?window_s=60`` selects the SLI snapshot window.  Every autopilot
    decision is also a sealed trace (GET /v1/trace/slo-<policy>-<seq>)
    so promotions and rollbacks are auditable like any request.

POST /v1/debug/profile   {"duration_ms"?: 1000, "mode"?: "auto"}
    -> 202 {"mode": "jax"|"python", "artifact": path, "duration_ms",
            "started_unix"}
    Starts a time-boxed capture and returns immediately; ``artifact`` is
    where it lands (a TensorBoard trace dir for jax mode, collapsed-stack
    JSON for python mode).  409 while a capture is already running; 503
    when profiling is disabled (no --profile-dir).
GET  /v1/debug/profile   -> {"active": {...}|null, "captures_total": n}
"""

from __future__ import annotations

import json
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

from repro.core.sampling import SamplingError, SamplingParams


# status -> (default error code, retryable) for the structured error
# taxonomy: every non-2xx body is {"error": {code, message, retryable,
# trace_id}} and clients retry ONLY retryable codes (instead of
# string-matching on the status line)
_STATUS_CODES: Dict[int, "tuple[str, bool]"] = {
    400: ("bad_request", False),
    403: ("forbidden", False),
    404: ("not_found", False),
    405: ("method_not_allowed", False),
    408: ("timeout", True),
    409: ("conflict", False),
    413: ("payload_too_large", False),
    429: ("queue_full", True),
    499: ("client_closed", False),
    500: ("internal", False),
    501: ("not_implemented", False),
    503: ("unavailable", True),
    504: ("deadline_exceeded", False),
}


def default_error_code(status: int) -> "tuple[str, bool]":
    """(code, retryable) defaults for a bare status."""
    if status in _STATUS_CODES:
        return _STATUS_CODES[status]
    if 400 <= status < 500:
        return "bad_request", False
    return "internal", False


class ApiError(Exception):
    """Route-layer failure; ``headers`` carries extras like Retry-After.

    ``code``/``retryable`` feed the structured error taxonomy; both
    default from the status so existing ``raise ApiError(...)`` sites
    stay correct without changes."""

    def __init__(self, status: int, message: str,
                 headers: Optional[Dict[str, str]] = None,
                 code: Optional[str] = None,
                 retryable: Optional[bool] = None):
        super().__init__(message)
        self.status = status
        self.message = message
        self.headers = headers or {}
        d_code, d_retry = default_error_code(status)
        self.code = code if code is not None else d_code
        self.retryable = retryable if retryable is not None else d_retry


def error_body(err: ApiError,
               trace_id: Optional[str] = None) -> Dict[str, Any]:
    """The structured non-2xx body: every error response carries a
    machine-readable code, whether a retry can help, and the trace id to
    pull the request's timeline."""
    return {"error": {
        "code": err.code,
        "message": err.message,
        "retryable": err.retryable,
        "trace_id": trace_id or err.headers.get("X-Request-Id"),
    }}


class JsonResponse:
    """A JSON payload plus extra response headers (e.g. ``X-Request-Id``).
    Route handlers that return a bare dict get the default headers."""

    def __init__(self, payload: Dict[str, Any],
                 headers: Optional[Dict[str, str]] = None,
                 status: int = 200):
        self.payload = payload
        self.headers = headers or {}
        self.status = status


class PlainTextResponse:
    """A non-JSON body (the Prometheus exposition)."""

    def __init__(self, text: str,
                 content_type: str = "text/plain; version=0.0.4; "
                                     "charset=utf-8",
                 status: int = 200):
        self.text = text
        self.content_type = content_type
        self.status = status


class StreamingResponse:
    """A route handler's signal to the HTTP layer: write ``events`` as a
    chunked-transfer NDJSON body (one event per chunk) instead of a single
    JSON document.  ``on_disconnect`` is invoked if the client goes away
    mid-stream (cancels the underlying request)."""

    def __init__(self, events: Iterator[Dict[str, Any]],
                 on_disconnect: Optional[Callable[[], Any]] = None,
                 headers: Optional[Dict[str, str]] = None):
        self.events = events
        self.headers: Dict[str, str] = headers or {}
        self._on_disconnect = on_disconnect

    def disconnect(self) -> None:
        if self._on_disconnect is not None:
            self._on_disconnect()


def parse_sampling(req: Dict[str, Any], *,
                   default_max_new_tokens: int = 16) -> SamplingParams:
    """Per-request sampling params from a /v1/generate body (400 on bad)."""
    try:
        return SamplingParams.from_request(
            req, default_max_new_tokens=default_max_new_tokens)
    except SamplingError as e:
        raise ApiError(400, str(e)) from None


def parse_request(body: bytes) -> Dict[str, Any]:
    try:
        obj = json.loads(body or b"{}")
    except json.JSONDecodeError as e:
        raise ApiError(400, f"invalid JSON: {e}") from None
    if not isinstance(obj, dict):
        raise ApiError(400, "request body must be a JSON object")
    return obj


def opt_int(req: Dict[str, Any], key: str, default: int) -> int:
    """Integer field with a 400 (not a 500) on malformed values."""
    val = req.get(key, default)
    try:
        return int(val)
    except (TypeError, ValueError):
        raise ApiError(400, f"{key!r} must be an integer, "
                            f"got {val!r}") from None


def to_jsonable(obj):
    if isinstance(obj, dict):
        return {k: to_jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [to_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return to_jsonable(obj.tolist())
    if isinstance(obj, (np.bool_,)):
        return bool(obj)
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if hasattr(obj, "tolist"):          # jax arrays
        return to_jsonable(np.asarray(obj))
    return obj


def encode_response(obj: Dict[str, Any]) -> bytes:
    return json.dumps(to_jsonable(obj)).encode()


def inputs_to_batch(inputs: Dict[str, Any]) -> Dict[str, np.ndarray]:
    if not isinstance(inputs, dict) or not inputs:
        raise ApiError(400, "'inputs' must be a non-empty object of arrays")
    batch = {}
    n = None
    for k, v in inputs.items():
        arr = np.asarray(v)
        if arr.dtype == np.float64:
            arr = arr.astype(np.float32)
        if arr.dtype == np.int64:
            arr = arr.astype(np.int32)
        if n is None:
            n = arr.shape[0]
        elif arr.shape[0] != n:
            raise ApiError(400, "all inputs must share the batch dimension")
        batch[k] = arr
    return batch
