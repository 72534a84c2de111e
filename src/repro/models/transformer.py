"""Generic decoder-only transformer: dense GQA / sliding-window / MoE / MLA.

Covers yi-9b, mistral-large-123b, command-r-plus-104b (parallel block),
h2o-danube-1.8b (native SWA), qwen3-moe (qk-norm + MoE), deepseek-v3
(MLA + first-k-dense + MoE + MTP), and the self-attention backbone reused
by the VLM and enc-dec families.

Layers are scanned with stacked params so the HLO stays O(1) in depth.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.layers import (
    apply_mlp, apply_norm, compute_dtype, cross_entropy_loss, dense_init,
    embed_init, init_mlp, init_norm, stack_init)
from repro.models.moe import init_moe, moe_layer
from repro.sharding import shard


# ---------------------------------------------------------------------------
# Layer init
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, *, moe: bool):
    ks = jax.random.split(key, 4)
    p = {"ln1": init_norm(cfg)}
    if cfg.attn_kind == "mla":
        p["attn"] = attn.init_mla(ks[0], cfg)
    else:
        p["attn"] = attn.init_attention(ks[0], cfg)
    if not cfg.parallel_block:
        p["ln2"] = init_norm(cfg)
    if moe:
        p["moe"] = init_moe(ks[1], cfg)
    else:
        d_ff = cfg.d_ff
        if cfg.moe and cfg.moe.first_k_dense and cfg.moe.d_ff_dense:
            d_ff = cfg.moe.d_ff_dense
        p["mlp"] = init_mlp(ks[1], cfg, d_ff=d_ff)
    return p


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dt = compute_dtype(cfg)
    ks = jax.random.split(key, 6)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dt),
        "final_norm": init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dt)
    n_dense = cfg.moe.first_k_dense if cfg.moe else cfg.num_layers
    n_moe = cfg.num_layers - n_dense if cfg.moe else 0
    if cfg.moe is None:
        params["layers"] = stack_init(
            ks[2], cfg.num_layers, init_layer, cfg, moe=False)
    else:
        if n_dense:
            params["dense_layers"] = stack_init(
                ks[2], n_dense, init_layer, cfg, moe=False)
        params["layers"] = stack_init(ks[3], n_moe, init_layer, cfg, moe=True)
    if cfg.mtp:
        params["mtp"] = {
            "proj": dense_init(ks[4], (2 * cfg.d_model, cfg.d_model), dt),
            "layer": stack_init(ks[5], 1, init_layer, cfg, moe=cfg.moe is not None),
            "norm_h": init_norm(cfg),
            "norm_e": init_norm(cfg),
        }
    return params


# ---------------------------------------------------------------------------
# Full-sequence layer application (train / prefill)
# ---------------------------------------------------------------------------


def _attn_full(lp, h, cfg, positions, kv_lengths, window):
    if cfg.attn_kind == "mla":
        return attn.mla_attention_block(lp["attn"], h, cfg,
                                        positions=positions,
                                        kv_lengths=kv_lengths)
    return attn.attention_block(lp["attn"], h, cfg, positions=positions,
                                causal=True, window=window,
                                kv_lengths=kv_lengths)


def _block_tail(cfg: ModelConfig, moe: bool, lp, x, h, attn_out,
                valid=None):
    """The residual adds and the feed-forward of one block, given its
    normed input ``h`` and attention output.  Returns (x, aux_loss, rows):
    rows (2,) int32 are an expert layer's (held, routed) rows of the
    ``valid`` tokens (``moe_layer``), None for a dense layer."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.parallel_block:
        return x + attn_out + apply_mlp(lp["mlp"], h, cfg), aux, None
    x = x + attn_out
    h2 = apply_norm(lp["ln2"], x, cfg)
    if moe:
        mo, aux, rows = moe_layer(lp["moe"], h2, cfg, valid=valid)
        return x + mo, aux, rows
    return x + apply_mlp(lp["mlp"], h2, cfg), aux, None


def _layer_full(cfg: ModelConfig, moe: bool, window, x, lp, positions,
                kv_lengths):
    """One block, full sequence. Returns (x, aux_loss)."""
    h = apply_norm(lp["ln1"], x, cfg)
    attn_out = _attn_full(lp, h, cfg, positions, kv_lengths, window)
    x, aux, _ = _block_tail(cfg, moe, lp, x, h, attn_out)
    # under seq_parallel the carried residual (and thus every remat-saved
    # activation) is sharded over `model` along seq (Megatron-SP)
    x = shard(x, "batch", "seq_sp", None)
    return x, aux


def _scan_stack(cfg, stacked, x, positions, kv_lengths, *, moe: bool,
                window, remat: bool):
    body = functools.partial(_layer_full, cfg, moe, window)

    def step(carry, lp):
        x, aux = carry
        x, a = body(x, lp, positions, kv_lengths)
        return (x, aux + a), None

    if remat:
        step = jax.checkpoint(step, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


def forward(params, tokens, cfg: ModelConfig, *, kv_lengths=None,
            window: Optional[int] = None, remat: bool = False,
            return_hidden: bool = False):
    """tokens (B,S) -> logits (B,S,V). ``window`` overrides cfg.sliding_window
    (the beyond-paper long-context SWA variant for dense archs)."""
    B, S = tokens.shape
    window = window if window is not None else cfg.sliding_window
    x = params["embed"][tokens]
    x = shard(x, "batch", None, None)
    positions = jnp.arange(S)[None, :]
    aux = jnp.zeros((), jnp.float32)
    if "dense_layers" in params:
        x, a = _scan_stack(cfg, params["dense_layers"], x, positions,
                           kv_lengths, moe=False, window=window, remat=remat)
        aux += a
    x, a = _scan_stack(cfg, params["layers"], x, positions, kv_lengths,
                       moe=cfg.moe is not None, window=window, remat=remat)
    aux += a
    h = apply_norm(params["final_norm"], x, cfg)
    logits = project_logits(params, h, cfg)
    if return_hidden:
        return logits, aux, h
    return logits, aux


def project_logits(params, h, cfg: ModelConfig):
    head = params["head"] if "head" in params else params["embed"].T
    logits = h @ head
    if logits.ndim == 2:                      # (B, V) — prefill/decode path
        return shard(logits, "batch", "vocab")
    return shard(logits, "batch", None, "vocab")


# ---------------------------------------------------------------------------
# Train loss (with optional deepseek MTP)
# ---------------------------------------------------------------------------


def train_loss(params, batch, cfg: ModelConfig, *, remat: bool = True):
    from repro import opt
    from repro.models.layers import chunked_cross_entropy
    tokens, labels = batch["tokens"], batch["labels"]
    mask = batch.get("mask")
    if opt.enabled("chunked_ce") and cfg.vocab_size >= 32768:
        # never materialize (B,S,V): stream the head matmul by vocab chunk
        _, aux, h = forward(params, tokens, cfg, remat=remat,
                            return_hidden=True)
        head = params["head"] if "head" in params else params["embed"].T
        loss = chunked_cross_entropy(h, head, labels, mask)
        logits = None
    else:
        logits, aux, h = forward(params, tokens, cfg, remat=remat,
                                 return_hidden=True)
        loss = cross_entropy_loss(logits, labels, mask)
    metrics = {"ce": loss, "aux": aux}
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * aux
    if cfg.mtp and "mtp" in params:
        mtp = params["mtp"]
        # predict t+2: combine h_t with embedding of label t (= token t+1)
        emb_next = params["embed"][labels]
        hm = jnp.concatenate([apply_norm(mtp["norm_h"], h, cfg),
                              apply_norm(mtp["norm_e"], emb_next, cfg)], -1)
        hm = hm @ mtp["proj"]
        positions = jnp.arange(tokens.shape[1])[None, :]
        hm, _ = _scan_stack(cfg, mtp["layer"], hm, positions, None,
                            moe=cfg.moe is not None, window=cfg.sliding_window,
                            remat=remat)
        mtp_logits = project_logits(params, apply_norm(
            params["final_norm"], hm, cfg), cfg)
        mtp_labels = jnp.concatenate(
            [labels[:, 1:], labels[:, -1:]], axis=1)      # labels shifted +1
        mtp_loss = cross_entropy_loss(mtp_logits, mtp_labels, mask)
        metrics["mtp"] = mtp_loss
        loss = loss + 0.3 * mtp_loss
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode path (serve_step): one token against a per-layer cache
# ---------------------------------------------------------------------------


def init_state(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None, window: Optional[int] = None) -> Dict[str, Any]:
    """``window`` + the ring_cache optimization shrink the KV cache to
    O(window) for sliding-window serving (danube native SWA; the
    beyond-paper SWA variant for dense archs on long_500k)."""
    from repro import opt
    window = window if window is not None else cfg.sliding_window
    if (window is not None and opt.enabled("ring_cache")
            and cfg.attn_kind != "mla"):
        max_len = min(max_len, window)
    n_dense = cfg.moe.first_k_dense if cfg.moe else 0
    n_main = cfg.num_layers - n_dense
    mk_cache = (attn.init_mla_cache if cfg.attn_kind == "mla"
                else attn.init_kv_cache)
    state: Dict[str, Any] = {}
    if cfg.moe is not None:
        # the last step's (held, routed) expert rows (``moe_layer``): read
        # by the host with the step's tokens, never fed back
        state["moe_rows"] = jnp.zeros((2,), jnp.int32)
    if n_dense:
        c = mk_cache(n_dense, batch, max_len, cfg, dtype)
        c.pop("length")
        state["cache_dense"] = c
    c = mk_cache(n_main, batch, max_len, cfg, dtype)
    c.pop("length")
    state["cache"] = c
    state["length"] = jnp.zeros((batch,), jnp.int32)
    return state


def _layer_decode(cfg: ModelConfig, moe: bool, window, x, lp, cache, layer,
                  lengths):
    """One block of the decode step.  Returns (x, cache, rows): rows are
    an expert layer's (held, routed) expert rows, None for a dense one."""
    h = apply_norm(lp["ln1"], x, cfg)
    if cfg.attn_kind == "mla":
        attn_out, cache = attn.mla_decode_block(lp["attn"], h, cache, layer,
                                                lengths, cfg)
    else:
        attn_out, cache = attn.stacked_decode_attn_block(
            lp["attn"], h, cache, layer, lengths, cfg, window=window)
    x, _, rows = _block_tail(cfg, moe, lp, x, h, attn_out)
    return x, cache, rows


def _scan_carried(layer_fn, stacked, cache, x):
    """Scan ``layer_fn(x, lp, cache, layer) -> (x, cache, ys)`` over the
    stacked params with the whole stacked cache in the CARRY: each layer
    writes its new tokens at ``[layer, ...]`` of the loop's one buffer, so
    a donated cache is updated in place, with no per-layer slice
    restacked into a scan output and no whole-cache copy after the loop.
    Returns (x, cache, ys stacked over the layers)."""
    num_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]

    def step(carry, xs):
        x, cache = carry
        lp, layer = xs
        x, cache, ys = layer_fn(x, lp, cache, layer)
        return (x, cache), ys

    (x, cache), ys = jax.lax.scan(step, (x, cache),
                                  (stacked, jnp.arange(num_layers)))
    return x, cache, ys


def _scan_decode(cfg, stacked, cache, x, lengths, *, moe: bool, window):
    layer_fn = functools.partial(_layer_decode, cfg, moe, window,
                                 lengths=lengths)
    return _scan_carried(layer_fn, stacked, cache, x)


def decode_step(params, token, state, cfg: ModelConfig, *,
                window: Optional[int] = None):
    """token (B,) int32 -> (logits (B,V), new state). Appends one position."""
    window = window if window is not None else cfg.sliding_window
    lengths = state["length"]
    x = params["embed"][token][:, None, :]                 # (B,1,D)
    x = shard(x, "batch", None, None)
    new_state = dict(state)
    if "cache_dense" in state:
        x, nc, _ = _scan_decode(cfg, params["dense_layers"],
                                state["cache_dense"], x, lengths, moe=False,
                                window=window)
        new_state["cache_dense"] = nc
    x, nc, rows = _scan_decode(cfg, params["layers"], state["cache"], x,
                               lengths, moe=cfg.moe is not None,
                               window=window)
    if cfg.moe is not None:
        new_state["moe_rows"] = rows.sum(0)
    new_state["cache"] = nc
    h = apply_norm(params["final_norm"], x, cfg)
    logits = project_logits(params, h, cfg)[:, 0]
    new_state["length"] = lengths + 1
    return logits, new_state


# ---------------------------------------------------------------------------
# Verify window (speculative decoding): W tokens against the cache, one pass
# ---------------------------------------------------------------------------


def _layer_verify(cfg: ModelConfig, moe: bool, window, x, lp, cache, layer,
                  lengths):
    """One block over a W-token verify window.  x (B, W, D).

    KV for ALL W input positions is written first; the attention for
    query i then masks to ``lengths + i + 1`` valid positions — exactly
    the state the sequential single-token step would have seen at step i
    (later window positions hold this window's writes instead of stale
    garbage, but both are masked to NEG_INF before the softmax, so the
    per-query outputs are bitwise the sequential ones).  The per-query
    attention runs as a static Python loop calling the same
    ``decode_attention_ref`` with the same (B, H, hd) shapes as the
    sequential path — never a fused multi-query einsum whose reduction
    order could differ."""
    B, W, _ = x.shape
    h = apply_norm(lp["ln1"], x, cfg)
    positions = lengths[:, None] + jnp.arange(W)[None, :]        # (B, W)
    q, k, v = attn.project_qkv(lp["attn"], h, cfg, positions=positions)
    cache = attn.stacked_write(cache, layer, positions, {"k": k, "v": v})
    ck, cv = attn.layer_kv(cache, layer, cfg)
    outs = [attn.decode_attention_ref(q[:, i], ck, cv, lengths + i + 1,
                                      window=window) for i in range(W)]
    out = jnp.stack(outs, axis=1).reshape(B, W,
                                          cfg.num_heads * cfg.head_dim)
    attn_out = out @ lp["attn"]["wo"] + lp["attn"].get("bo", 0.0)
    x, _, _ = _block_tail(cfg, moe, lp, x, h, attn_out)
    return x, cache, None


def _scan_verify(cfg, stacked, cache, x, lengths, *, moe: bool, window):
    layer_fn = functools.partial(_layer_verify, cfg, moe, window,
                                 lengths=lengths)
    return _scan_carried(layer_fn, stacked, cache, x)[:2]


def verify_decode_step(params, tokens, state, cfg: ModelConfig, *,
                       window: Optional[int] = None):
    """Speculative verify: W tokens (B, W) -> (logits (B, W, V), state).

    Row [b, i] of the logits is the next-token distribution after
    consuming ``tokens[b, :i+1]`` — bitwise what ``decode_step`` would
    emit if fed those tokens one at a time.  KV for every window position
    is written (accepted positions are thereby committed; rejected ones
    are dead weight masked out by the caller's accepted length — the
    rollback is a length update, no cache mutation).  ``state["length"]``
    is NOT advanced here: the speculative step owns the accepted-length
    accounting.  Requires a non-ring cache (window=None serving)."""
    window = window if window is not None else cfg.sliding_window
    lengths = state["length"]
    x = params["embed"][tokens]                            # (B, W, D)
    x = shard(x, "batch", None, None)
    new_state = dict(state)
    if "cache_dense" in state:
        x, nc = _scan_verify(cfg, params["dense_layers"],
                             state["cache_dense"], x, lengths, moe=False,
                             window=window)
        new_state["cache_dense"] = nc
    x, nc = _scan_verify(cfg, params["layers"], state["cache"], x, lengths,
                         moe=cfg.moe is not None, window=window)
    new_state["cache"] = nc
    h = apply_norm(params["final_norm"], x, cfg)
    logits = project_logits(params, h, cfg)                # (B, W, V)
    return logits, new_state


# ---------------------------------------------------------------------------
# Prefill: full-sequence forward that also fills the cache
# ---------------------------------------------------------------------------


def prefill(params, tokens, state, cfg: ModelConfig, *, lengths=None,
            window: Optional[int] = None):
    """Process a (right-padded) prompt batch, filling the decode cache.

    tokens (B,S); lengths (B,) valid lengths (default: all S).
    Returns (last-position logits (B,V), new state)."""
    B, S = tokens.shape
    window = window if window is not None else cfg.sliding_window
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    x = params["embed"][tokens]
    x = shard(x, "batch", None, None)
    positions = jnp.arange(S)[None, :]
    new_state = dict(state)

    # real (unpadded) tokens, for the expert layers' row counts
    valid = positions < lengths[:, None] if cfg.moe is not None else None

    def run_stack(x, stacked, cache, moe):
        def step(x, xs):
            lp, cache_layer = xs
            h = apply_norm(lp["ln1"], x, cfg)
            if cfg.attn_kind == "mla":
                attn_out, c_kv, k_rope = attn.mla_prefill_block(
                    lp["attn"], h, cfg, positions=positions,
                    kv_lengths=lengths)
                Smax = cache_layer["ckv"].shape[1]
                pad = [(0, 0), (0, Smax - S), (0, 0)]
                new_cache = {   # as init_mla_cache
                    "ckv": jnp.pad(c_kv, pad).astype(cache_layer["ckv"].dtype),
                    "krope": jnp.pad(attn.pad_rope(k_rope, cfg), pad).astype(
                        cache_layer["krope"].dtype),
                }
            else:
                q, k, v = attn.project_qkv(lp["attn"], h, cfg,
                                           positions=positions)
                mask = attn.make_mask(S, S, causal=True, window=window,
                                      kv_lengths=lengths)
                out = attn.gqa_attention(q, k, v, mask)
                out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
                attn_out = out @ lp["attn"]["wo"] + lp["attn"].get("bo", 0.0)
                Smax = cache_layer["k"].shape[1]
                ring = Smax < S or (window is not None and Smax <= window)

                def fill(t, like):
                    if ring:   # ring cache: keep only the last `Smax` positions
                        t = attn.ring_fill(t, lengths, Smax)
                    else:
                        t = jnp.pad(t, [(0, 0), (0, Smax - S), (0, 0), (0, 0)])
                    # stored heads-flat (B, Smax, K*hd), as init_kv_cache
                    return t.reshape(like.shape).astype(like.dtype)

                new_cache = {"k": fill(k, cache_layer["k"]),
                             "v": fill(v, cache_layer["v"])}
            x2, _, rows = _block_tail(cfg, moe, lp, x, h, attn_out,
                                      valid=valid)
            x2 = shard(x2, "batch", None, None)
            return x2, (new_cache, rows)

        x, (cache, rows) = jax.lax.scan(step, x, (stacked, cache))
        return x, cache, (None if rows is None else rows.sum(0))

    if "cache_dense" in state:
        x, nc, _ = run_stack(x, params["dense_layers"], state["cache_dense"],
                             False)
        new_state["cache_dense"] = nc
    x, nc, rows = run_stack(x, params["layers"], state["cache"],
                            cfg.moe is not None)
    new_state["cache"] = nc
    if cfg.moe is not None:
        new_state["moe_rows"] = rows
    h = apply_norm(params["final_norm"], x, cfg)
    # logits at each row's last valid position
    rows = jnp.arange(B)
    h_last = h[rows, lengths - 1]
    logits = project_logits(params, h_last, cfg)
    new_state["length"] = lengths
    return logits, new_state
