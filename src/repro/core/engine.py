"""InferenceEngine: bucketed prefill + jitted autoregressive decode.

One engine serves one model (the Ensemble wraps several).  The engine owns
the decode state (KV cache / recurrent state), donates it through the jitted
decode step so caches update in place, and buckets prompt lengths and batch
sizes so arbitrary client requests hit a bounded jit cache (paper §2.3 on
XLA terms).

The decode data path is DEVICE-RESIDENT: ``decode_sample`` fuses the
model's decode step with vectorized per-row sampling (repro.core.sampling)
into one jitted program, so per tick only the sampled token ids —
``(batch,)`` int32 — cross device→host, never the ``(batch, vocab)``
logits.  Per-row sampling settings (temperature / top_k / top_p / rng key)
are traced ARRAY arguments: heterogeneous requests share the one compiled
step with no recompiles.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batching import BucketSpec, pad_sequences
from repro.core.sampling import (SamplingParams, base_key, sample_tokens,
                                 samplers_for)
from repro.core.telemetry import span
from repro.models.build import Model


@dataclass
class GenerationResult:
    tokens: List[List[int]]            # new tokens per row
    prompt_lengths: List[int]
    steps: int
    finish_reasons: Optional[List[Optional[str]]] = None


class InferenceEngine:
    def __init__(self, model: Model, params, *, max_len: int = 2048,
                 max_batch: int = 8, window: Optional[int] = None,
                 donate_state: bool = True):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.window = window
        self.batch_buckets = BucketSpec.pow2(max_batch)
        self.seq_buckets = BucketSpec.pow2(max_len, min_size=16)
        # forward-call accounting (batched prefill shows up as fewer
        # prefill calls than admitted requests)
        self.prefill_calls = 0

        kw = {}
        if window is not None:
            kw["window"] = window
        self._prefill = jax.jit(
            functools.partial(model.prefill, **kw))
        self._decode = jax.jit(
            functools.partial(model.decode, **kw),
            donate_argnums=(2,) if donate_state else ())

        def decode_and_sample(params_, token, state, temp, top_k, top_p,
                              key, ctr):
            logits, state = model.decode(params_, token, state, **kw)
            toks = sample_tokens(logits, temp, top_k, top_p, key, ctr)
            # returning ctr+1 keeps the token counters DEVICE-RESIDENT
            # across ticks: steady-state decode uploads nothing
            return toks, state, ctr + 1

        self._decode_sample = jax.jit(
            decode_and_sample,
            donate_argnums=(2,) if donate_state else ())
        self._sample = jax.jit(sample_tokens)
        self._state_axes = None
        self._insert_rows = None

    # --- API -----------------------------------------------------------------

    def new_state(self, batch: int):
        return self.model.init_state(batch, self.max_len)

    def prefill(self, batch: Dict[str, Any], state):
        self.prefill_calls += 1
        with span("prefill"):
            return self._prefill(self.params, batch, state)

    def decode(self, token, state):
        with span("decode"):
            return self._decode(self.params, token, state)

    def decode_sample(self, token, state, samp: Dict[str, Any], ctr):
        """One fused decode tick: model decode step + on-device sampling.
        ``samp`` holds the per-row arrays (temperature/top_k/top_p/key),
        ``ctr`` the per-row token counters.  Returns ``(token_ids (B,)
        int32 device array, new_state, ctr+1)`` — the ids are the ONLY
        thing a caller needs to pull to host; ids and counters feed the
        next tick without leaving the device."""
        with span("decode_sample"):
            return self._decode_sample(self.params, token, state,
                                       samp["temperature"], samp["top_k"],
                                       samp["top_p"], samp["key"], ctr)

    def sample(self, logits, samp: Dict[str, Any], ctr):
        """On-device sampling of standalone logits (the prefill first-token
        path); same per-row contract as ``decode_sample``."""
        with span("sample"):
            return self._sample(logits, samp["temperature"],
                                samp["top_k"], samp["top_p"],
                                samp["key"], ctr)

    def decode_cache_size(self) -> Optional[int]:
        """Compiled-variant count of the fused decode step (None when this
        jax build has no cache introspection).  Tests pin it flat across
        ticks with heterogeneous sampling params."""
        probe = getattr(self._decode_sample, "_cache_size", None)
        return probe() if callable(probe) else None

    def insert_rows(self, pool_state, group_state, src_rows, write_mask):
        """One-call slot scatter: copy selected rows of a freshly
        prefilled GROUP state into selected slots of a pooled decode
        state.  ``src_rows``/``write_mask`` are dense per-slot vectors:
        slot b takes group row ``src_rows[b]`` iff ``write_mask[b]`` —
        one compiled program per group-batch bucket covers every
        admission pattern.  The jit cache lives on the ENGINE so every
        scheduler (and warm-up pass) over this engine shares it."""
        if self._insert_rows is None:
            batch_axes = self.state_batch_axes()

            def insert(pool_state, group_state, src_rows, write_mask):
                def one(pool, sub, axis):
                    if axis is None:       # no batch axis: keep the pool's
                        return pool
                    pool_m = jnp.moveaxis(pool, axis, 0)
                    sub_m = jnp.moveaxis(sub, axis, 0)
                    picked = jnp.take(sub_m, src_rows, axis=0)
                    mask = write_mask.reshape(
                        (-1,) + (1,) * (pool_m.ndim - 1))
                    out = jnp.where(mask, picked.astype(pool_m.dtype),
                                    pool_m)
                    return jnp.moveaxis(out, 0, axis)

                return jax.tree_util.tree_map(one, pool_state, group_state,
                                              batch_axes)

            self._insert_rows = jax.jit(insert)
        with span("insert_rows"):
            return self._insert_rows(pool_state, group_state, src_rows,
                                     write_mask)

    def state_batch_axes(self):
        """Per-leaf batch-axis pytree of the decode state, found by
        comparing abstract state shapes at two batch sizes (no
        allocation).  Some families keep batch off axis 0 — rwkv state
        leaves are (layers, batch, ...) — so slot scatter can't assume."""
        if self._state_axes is None:
            s2 = jax.eval_shape(lambda: self.model.init_state(2,
                                                              self.max_len))
            s3 = jax.eval_shape(lambda: self.model.init_state(3,
                                                              self.max_len))
            self._state_axes = jax.tree_util.tree_map(
                lambda a, b: next(
                    (i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                     if x != y), None),
                s2, s3)
        return self._state_axes

    def generate(self, prompts: Sequence[Sequence[int]], *,
                 max_new_tokens: int = 32, eos_id: Optional[int] = None,
                 extras: Optional[Dict[str, Any]] = None,
                 sampling: Optional[SamplingParams] = None,
                 device_sampling: bool = True) -> GenerationResult:
        """Generation for a variable-size batch of variable-length prompts
        (greedy by default; ``sampling`` selects per-row temperature /
        top-k / top-p decoding).  Batch and prompt length are bucketed;
        rows beyond the real batch are masked out of the result.

        With ``device_sampling`` (default) every step samples on device
        through the fused decode step — row i of a seeded request draws
        token j with ``fold_in(PRNGKey(seed + i), j)``, the same stream
        the continuous-batching scheduler derives, so a request decodes
        identically here and under slot admission.  ``device_sampling=
        False`` keeps the numpy ``TokenSampler`` reference path."""
        if sampling is None:
            sampling = SamplingParams(max_new_tokens=max_new_tokens,
                                      eos_id=eos_id)
        n = len(prompts)
        B = self.batch_buckets.bucket_for(n)
        tokens, lengths = pad_sequences(prompts, self.seq_buckets)
        tokens = np.asarray(pad_batch_rows(tokens, B))
        lengths = np.asarray(pad_batch_rows(lengths, B, fill=1))
        state = self.new_state(B)
        batch = {"tokens": jnp.asarray(tokens),
                 "lengths": jnp.asarray(lengths)}
        if extras:
            batch.update({k: _pad_rows(v, B) for k, v in extras.items()})
        logits, state = self.prefill(batch, state)
        if device_sampling:
            return self._generate_device(prompts, sampling, logits, state)
        return self._generate_host(prompts, sampling, logits, state)

    def _generate_device(self, prompts, sampling: SamplingParams,
                         logits, state) -> GenerationResult:
        """Device-resident decode loop: per step, only (B,) token ids
        cross to host (sampled fused with the decode step)."""
        n = len(prompts)
        B = logits.shape[0]
        row_params = [sampling.for_row(i) for i in range(n)]
        samplers = [p.sampler() for p in row_params]       # is_stop only
        temps = np.zeros((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        top_ps = np.ones((B,), np.float32)
        keys = np.zeros((B, 2), np.uint32)
        for i, p in enumerate(row_params):
            temps[i] = p.temperature
            top_ks[i] = p.top_k
            top_ps[i] = p.top_p
            keys[i] = base_key(p.resolve_seed())
        samp = {"temperature": jnp.asarray(temps),
                "top_k": jnp.asarray(top_ks),
                "top_p": jnp.asarray(top_ps),
                "key": jnp.asarray(keys)}
        out: List[List[int]] = [[] for _ in range(n)]
        reasons: List[Optional[str]] = [None] * n
        done = np.zeros((n,), bool)
        steps = 0
        # ctr is uniform across rows: a live row has produced exactly
        # `step` tokens when token `step` is sampled (done rows ignore it)
        ctr = jnp.zeros((B,), jnp.int32)
        tok_dev = self.sample(logits, samp, ctr)
        ctr = ctr + 1
        for _ in range(sampling.max_new_tokens):
            host = np.asarray(tok_dev)                     # (B,) int32
            for i in range(n):
                if done[i]:
                    continue
                t = int(host[i])
                out[i].append(t)
                if samplers[i].is_stop(t):
                    done[i] = True
                    reasons[i] = ("eos" if sampling.eos_id is not None
                                  and t == sampling.eos_id else "stop")
                elif len(out[i]) >= sampling.max_new_tokens:
                    done[i] = True
                    reasons[i] = "length"
            steps += 1
            if done.all():
                break
            tok_dev, state, ctr = self.decode_sample(tok_dev, state,
                                                     samp, ctr)
        return GenerationResult(tokens=out,
                                prompt_lengths=[len(p) for p in prompts],
                                steps=steps, finish_reasons=reasons)

    def _generate_host(self, prompts, sampling: SamplingParams,
                       logits, state) -> GenerationResult:
        """Reference decode loop: numpy TokenSampler on host logits."""
        n = len(prompts)
        B = logits.shape[0]
        samplers = samplers_for(sampling, n)
        out: List[List[int]] = [[] for _ in range(n)]
        reasons: List[Optional[str]] = [None] * n
        done = np.zeros((n,), bool)
        steps = 0
        next_host = np.zeros((B,), np.int32)
        for _ in range(sampling.max_new_tokens):
            if sampling.greedy:
                # argmax on device: only B ints cross to host per step
                host_logits = None
                greedy = np.asarray(jnp.argmax(logits, -1), np.int32)
            else:
                host_logits = np.asarray(logits)               # (B, V)
            for i in range(n):
                if done[i]:
                    continue
                t = (int(greedy[i]) if host_logits is None
                     else samplers[i].sample(host_logits[i]))
                out[i].append(t)
                next_host[i] = t
                if samplers[i].is_stop(t):
                    done[i] = True
                    reasons[i] = ("eos" if sampling.eos_id is not None
                                  and t == sampling.eos_id else "stop")
                elif len(out[i]) >= sampling.max_new_tokens:
                    done[i] = True
                    reasons[i] = "length"
            steps += 1
            if done.all():
                break
            logits, state = self.decode(jnp.asarray(next_host), state)
        return GenerationResult(tokens=out,
                                prompt_lengths=[len(p) for p in prompts],
                                steps=steps, finish_reasons=reasons)


class PagedInferenceEngine(InferenceEngine):
    """InferenceEngine whose decode state is a block-paged KV pool.

    Same public decode contract as the dense engine — ``decode_sample`` /
    ``sample`` / ``decode_cache_size`` are inherited unchanged, so the
    scheduler's decode tick is identical — but the state carries a shared
    ``(layers, num_pages, page_size, K, hd)`` page pool plus a per-slot
    ``(num_slots, max_pages_per_seq)`` page table instead of per-slot
    worst-case caches.  Page bookkeeping (allocation, refcounts, prefix
    sharing) lives host-side in the scheduler's ``KVPager``; this class
    owns only the jitted device programs.

    Prefill is context-aware: ``paged_prefill`` runs the SUFFIX of each
    prompt (what its shared prefix doesn't cover) and commits the new KV
    straight into freshly allocated pool pages — there is no per-group
    cache to scatter with ``insert_rows`` afterwards."""

    def __init__(self, model: Model, params, *, max_len: int = 2048,
                 max_batch: int = 8, window: Optional[int] = None,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 donate_state: bool = True):
        from repro.core.kv_pager import pages_for_budget
        from repro.models.paged import (init_paged_state, paged_decode_step,
                                        paged_prefill, supports_paging)
        cfg = model.config
        if not supports_paging(cfg):
            raise ValueError(f"{cfg.name}: no paged KV path for family "
                             f"{cfg.family}/{cfg.attn_kind}")
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} not a multiple of "
                             f"page_size {page_size}")
        super().__init__(model, params, max_len=max_len, max_batch=max_batch,
                         window=window, donate_state=donate_state)
        self.paged = True
        self.page_size = page_size
        self.max_pages_per_seq = max_len // page_size
        self.page_bytes = page_kv_bytes(cfg, page_size)
        if num_pages is None:
            if hbm_budget_bytes is not None:
                num_pages = pages_for_budget(hbm_budget_bytes,
                                             self.page_bytes)
            else:
                # dense-equivalent worst case + the reserved dump page
                num_pages = max_batch * self.max_pages_per_seq + 1
        if num_pages - 1 < self.max_pages_per_seq:
            raise ValueError(
                f"{num_pages} pages cannot hold even one max-length "
                f"sequence ({self.max_pages_per_seq} pages)")
        self.num_pages = num_pages
        # context-page-count buckets for the shared-prefix prefill variants
        self.ctx_buckets = BucketSpec.pow2(self.max_pages_per_seq,
                                           min_size=1)
        self._init_paged_state = init_paged_state

        kw: Dict[str, Any] = {"page_size": page_size}
        if window is not None:
            kw["window"] = window
        self._decode = jax.jit(
            functools.partial(
                lambda p_, t, s, **k: paged_decode_step(p_, t, s, cfg, **k),
                **kw),
            donate_argnums=(2,) if donate_state else ())

        def decode_and_sample(params_, token, state, temp, top_k, top_p,
                              key, ctr):
            logits, state = paged_decode_step(params_, token, state, cfg,
                                              **kw)
            toks = sample_tokens(logits, temp, top_k, top_p, key, ctr)
            return toks, state, ctr + 1

        self._decode_sample = jax.jit(
            decode_and_sample,
            donate_argnums=(2,) if donate_state else ())

        def prefill_fn(params_, tokens, lengths, state, ctx_table, ctx_lens,
                       dest_table):
            return paged_prefill(params_, tokens, lengths, state, ctx_table,
                                 ctx_lens, dest_table, cfg, **kw)

        self._paged_prefill = jax.jit(
            prefill_fn, donate_argnums=(3,) if donate_state else ())

    def ctx_bucket_for(self, n_ctx_pages: int) -> int:
        """Bucketed context-page count (0 stays 0: the no-sharing prefill
        variant is exactly the dense computation)."""
        if n_ctx_pages == 0:
            return 0
        return self.ctx_buckets.bucket_for(n_ctx_pages)

    def new_state(self, batch: int):
        return self._init_paged_state(self.model.config, batch,
                                      self.num_pages, self.page_size,
                                      self.max_pages_per_seq)

    def paged_prefill(self, state, tokens, lengths, ctx_table, ctx_lens,
                      dest_table):
        """Suffix prefill into pool pages.  ``tokens``/``lengths`` are the
        bucketed per-row suffixes, ``ctx_table`` the shared prefix pages
        each row attends to, ``dest_table`` the pages the new KV lands in.
        Returns ``(first-token logits, new state)`` — the pool is updated
        in place (donated); table/length device arrays pass through."""
        self.prefill_calls += 1
        with span("paged_prefill"):
            return self._paged_prefill(self.params, tokens, lengths, state,
                                       ctx_table, ctx_lens, dest_table)

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "PagedInferenceEngine has no standalone generate(): page "
            "allocation lives in the scheduler — drive it through "
            "ContinuousBatchingScheduler / SchedulerService")


class SpeculativeEngine(InferenceEngine):
    """Draft-propose / target-verify pair behind the one-engine contract.

    Wraps a TARGET engine (whose streams are the product) and a smaller
    DRAFT engine of the same family.  Per speculative tick, a jitted
    per-window-size program:

      1. scans the draft W steps (greedy argmax proposals — the draft's
         KV advances through the window, its last sample is discarded),
      2. runs the target's verify forward over the W-token window in ONE
         batched pass (KV for every window position committed in place),
      3. accepts/rejects ON DEVICE via exact-match against the
         sequential draws (``speculative_accept``, PR 5 fold_in RNG) —
         rejected positions roll back as a pure length update,

    and returns (draws, counts, next_token, state, ctr+counts): only
    token ids and per-slot accepted counts ever cross to host.  Seeded
    streams are byte-identical to non-speculative decoding by
    construction (greedy exact, sampled draw-for-draw).

    The combined decode state nests both engines' caches under one
    shared ``length`` (and, when paged, ONE shared ``page_table`` —
    draft and target pools are indexed by the same pages, so prefix
    sharing, park-pinning and rollback cover the pair for free; the
    draft pool is physically smaller via its fewer layers/heads).

    ``decode_sample`` (the non-speculative tick, also the adaptive-k
    level-1 backoff) reuses the TARGET's fused decode program on a view
    of the combined state — no extra compiled step, so mixed
    speculative/non-speculative traffic keeps ``compiled_steps`` flat.
    Level-1 ticks skip the draft entirely; its KV goes stale for those
    positions, which can only lower acceptance (never correctness) until
    the slot turns over.

    Constraints: dense GQA transformer family, no sliding window (the
    verify window's multi-position writes don't compose with ring
    caches), draft/target share vocab, max_len and — when paged — page
    geometry.
    """

    def __init__(self, target: InferenceEngine, draft: InferenceEngine, *,
                 max_window: int = 4):
        # NOTE: deliberately no super().__init__ — the pair's jitted
        # programs are the sub-engines' plus the per-level spec steps.
        tcfg = target.model.config
        dcfg = draft.model.config
        for name, cfg, eng in (("target", tcfg, target),
                               ("draft", dcfg, draft)):
            if cfg.family != "dense" or cfg.attn_kind != "gqa":
                raise ValueError(
                    f"speculative {name} must be a dense GQA transformer, "
                    f"got {cfg.family}/{cfg.attn_kind}")
            if cfg.sliding_window is not None or eng.window is not None:
                raise ValueError(
                    f"speculative {name} cannot use a sliding window")
        if tcfg.vocab_size != dcfg.vocab_size:
            raise ValueError(
                f"draft vocab {dcfg.vocab_size} != target vocab "
                f"{tcfg.vocab_size}")
        if target.max_len != draft.max_len:
            raise ValueError(
                f"draft max_len {draft.max_len} != target {target.max_len}")
        self.paged = bool(getattr(target, "paged", False))
        if self.paged != bool(getattr(draft, "paged", False)):
            raise ValueError("draft and target must both be paged or dense")
        if self.paged:
            for attr in ("page_size", "num_pages", "max_pages_per_seq"):
                if getattr(target, attr) != getattr(draft, attr):
                    raise ValueError(
                        f"draft {attr} {getattr(draft, attr)} != target "
                        f"{getattr(target, attr)} (the pair shares one "
                        f"page table)")
            self.page_size = target.page_size
            self.max_pages_per_seq = target.max_pages_per_seq
            self.num_pages = target.num_pages
            # admission cost of a page now covers both pools
            self.page_bytes = target.page_bytes + draft.page_bytes
            self.ctx_buckets = target.ctx_buckets
        if max_window < 2:
            raise ValueError(f"max_window must be >= 2, got {max_window}")
        self.target = target
        self.draft = draft
        self.model = target.model
        self.params = target.params
        self.max_len = target.max_len
        self.window = None
        self.batch_buckets = target.batch_buckets
        self.seq_buckets = target.seq_buckets
        self.prefill_calls = 0
        self._sample = target._sample
        self._state_axes = None
        self._insert_rows = None
        self.speculative = True
        # adaptive-k ladder: 1 (plain target tick) then powers of two
        self.spec_levels = [1]
        w = 2
        while w <= max_window:
            self.spec_levels.append(w)
            w *= 2
        self.max_window = self.spec_levels[-1]
        self._spec_steps: Dict[int, Any] = {}
        # draft/verify device-ms split estimate for telemetry: per-token
        # work is roughly proportional to parameter bytes streamed
        t_bytes = _param_bytes(target.params)
        d_bytes = _param_bytes(draft.params)
        self.draft_share = d_bytes / max(t_bytes + d_bytes, 1)

    # --- combined-state plumbing ---------------------------------------------

    @property
    def _shared_keys(self):
        return ("length", "page_table") if self.paged else ("length",)

    def _view(self, state, which: str):
        return {**state[which],
                **{k: state[k] for k in self._shared_keys}}

    def _caches(self, view):
        return {k: v for k, v in view.items() if k not in self._shared_keys}

    def _combine(self, tview, dview):
        out = {"target": self._caches(tview),
               "draft": self._caches(dview)}
        for k in self._shared_keys:
            out[k] = tview[k]
        return out

    def new_state(self, batch: int):
        t = self.target.new_state(batch)
        d = self.draft.new_state(batch)
        return self._combine(t, d)

    def state_batch_axes(self):
        if self._state_axes is None:
            s2 = jax.eval_shape(lambda: self.new_state(2))
            s3 = jax.eval_shape(lambda: self.new_state(3))
            self._state_axes = jax.tree_util.tree_map(
                lambda a, b: next(
                    (i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                     if x != y), None),
                s2, s3)
        return self._state_axes

    # --- prefill / decode ----------------------------------------------------

    def prefill(self, batch: Dict[str, Any], state):
        """Both halves of the pair prefill (the draft must see the prompt
        to propose); the TARGET's first-token logits are the product."""
        self.prefill_calls += 1
        logits, new_t = self.target.prefill(batch, self._view(state,
                                                              "target"))
        _, new_d = self.draft.prefill(batch, self._view(state, "draft"))
        return logits, self._combine(new_t, new_d)

    def paged_prefill(self, state, tokens, lengths, ctx_table, ctx_lens,
                      dest_table):
        """Paged pair prefill: the draft runs first and its pass-through
        length/page_table arrays re-seed the target's view (each paged
        prefill donates its state, so the shared arrays must be re-taken
        from the returned state between the two calls)."""
        self.prefill_calls += 1
        _, new_d = self.draft.paged_prefill(
            self._view(state, "draft"), tokens, lengths, ctx_table,
            ctx_lens, dest_table)
        tview = {**state["target"], "length": new_d["length"],
                 "page_table": new_d["page_table"]}
        logits, new_t = self.target.paged_prefill(
            tview, tokens, lengths, ctx_table, ctx_lens, dest_table)
        return logits, self._combine(new_t, new_d)

    def decode(self, token, state):
        logits, new_t = self.target.decode(token, self._view(state,
                                                             "target"))
        return logits, self._combine(new_t,
                                     self._view_stale_draft(state, new_t))

    def _view_stale_draft(self, state, new_tview):
        # level-1 / plain ticks advance only the target; the draft keeps
        # its (now stale) caches and follows the shared length
        return {**state["draft"],
                **{k: new_tview[k] for k in self._shared_keys}}

    def decode_sample(self, token, state, samp: Dict[str, Any], ctr):
        """Non-speculative tick on the pair: the TARGET's own fused
        decode-sample program over a view of the combined state — level-1
        backoff compiles nothing new."""
        with span("decode_sample"):
            toks, new_t, ctr2 = self.target._decode_sample(
                self.target.params, token, self._view(state, "target"),
                samp["temperature"], samp["top_k"], samp["top_p"],
                samp["key"], ctr)
        return toks, self._combine(new_t,
                                   self._view_stale_draft(state, new_t)), \
            ctr2

    # --- the speculative tick ------------------------------------------------

    def speculative_step(self, w: int, token, state, samp: Dict[str, Any],
                         ctr, spec_on):
        """One draft-propose + verify + accept tick at window size ``w``
        (a spec level >= 2).  Returns ``(draws (B, w), counts (B),
        next_token (B), new_state, ctr + counts)`` — row b emitted
        ``draws[b, :counts[b]]``; rows with ``spec_on[b]`` False advance
        exactly one (sequential-identical) token."""
        fn = self._spec_steps.get(w)
        if fn is None:
            fn = self._spec_steps[w] = self._build_spec_step(w)
        with span("speculative_step"):
            return fn(self.target.params, self.draft.params, state, token,
                      samp["temperature"], samp["top_k"], samp["top_p"],
                      samp["key"], ctr, spec_on)

    def _build_spec_step(self, W: int):
        from repro.core.sampling import speculative_accept
        from repro.models.paged import paged_decode_step, paged_verify_step
        from repro.models.transformer import verify_decode_step
        target, draft, paged = self.target, self.draft, self.paged
        tcfg = target.model.config
        dcfg = draft.model.config
        shared_keys = self._shared_keys
        if paged:
            ps = self.page_size

            def d_decode(p, tok, s):
                return paged_decode_step(p, tok, s, dcfg, page_size=ps)

            def t_verify(p, toks, s):
                return paged_verify_step(p, toks, s, tcfg, page_size=ps)
        else:
            def d_decode(p, tok, s):
                return draft.model.decode(p, tok, s)

            def t_verify(p, toks, s):
                return verify_decode_step(p, toks, s, tcfg)

        def spec_step(tp, dp, state, token, temp, top_k, top_p, key, ctr,
                      spec_on):
            shared = {k: state[k] for k in shared_keys}
            dview = {**state["draft"], **shared}

            # draft scan: W greedy proposals from the last emitted token.
            # All W iterations WRITE draft KV (the final sample is
            # discarded), so a fully-accepted window leaves the draft
            # cache sequentially exact for the next tick.
            def draft_iter(carry, _):
                tok, dv = carry
                logits, dv = d_decode(dp, tok, dv)
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (nxt, dv), nxt

            (_, dview), props = jax.lax.scan(draft_iter, (token, dview),
                                             None, length=W)
            drafts = props[:W - 1].T                        # (B, W-1)
            window_toks = jnp.concatenate(
                [token[:, None], drafts], axis=1)           # (B, W)
            tview = {**state["target"], **shared}
            vlogits, tview = t_verify(tp, window_toks, tview)
            draws, counts = speculative_accept(
                vlogits, drafts, temp, top_k, top_p, key, ctr)
            counts = jnp.where(spec_on, counts, 1)
            rows = jnp.arange(token.shape[0])
            next_tok = draws[rows, counts - 1]
            new_state = {"target": {k: v for k, v in tview.items()
                                    if k not in shared_keys},
                         "draft": {k: v for k, v in dview.items()
                                   if k not in shared_keys},
                         "length": state["length"] + counts}
            if paged:
                new_state["page_table"] = state["page_table"]
            return draws, counts, next_tok, new_state, ctr + counts

        return jax.jit(spec_step, donate_argnums=(2,))

    # --- introspection --------------------------------------------------------

    def decode_cache_size(self) -> Optional[int]:
        """Total compiled decode-tick variants across the pair: the
        target's fused step (also the level-1 path) plus one program per
        speculative window size."""
        total = 0
        fns = [self.target._decode_sample] + list(self._spec_steps.values())
        for fn in fns:
            probe = getattr(fn, "_cache_size", None)
            if not callable(probe):
                return None
            total += probe()
        return total

    def ctx_bucket_for(self, n_ctx_pages: int) -> int:
        if n_ctx_pages == 0:
            return 0
        return self.ctx_buckets.bucket_for(n_ctx_pages)

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "SpeculativeEngine has no standalone generate(): drive it "
            "through ContinuousBatchingScheduler / SchedulerService")


def _param_bytes(params) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(params))


def page_kv_bytes(cfg, page_size: int) -> int:
    """HBM bytes one KV page costs across every layer (k and v)."""
    from repro.models.attention import cache_dtype
    itemsize = jnp.dtype(cache_dtype(cfg)).itemsize
    return (cfg.num_layers * page_size * cfg.num_kv_heads * cfg.head_dim *
            itemsize * 2)


def pad_batch_rows(arr: np.ndarray, n: int, fill=0) -> np.ndarray:
    if arr.shape[0] == n:
        return arr
    pad = [(0, n - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad, constant_values=fill)


def _pad_rows(x, n):
    x = np.asarray(x)
    return jnp.asarray(pad_batch_rows(x, n))
