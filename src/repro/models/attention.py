"""Attention variants: GQA (full / causal / sliding-window / cross) and
DeepSeek-style MLA (Multi-head Latent Attention) with an absorbed decode path.

All functions are pure-jnp reference paths; the Pallas kernels in
``repro.kernels`` implement the same math for the TPU hot spots and are
swapped in by the engine when ``use_pallas=True``.

Shapes: x (B, S, D); q (B, S, H, hd); k/v (B, S, K, hd); GQA groups G=H/K.
Per-layer KV caches are (B, Smax, K, hd) with per-row valid ``lengths``;
the decoder-only transformer's stacked dense cache is (L, B, Smax, K*hd)
(``init_kv_cache``), written in place one token per row and read one layer
at a time as (B, Smax, K, hd).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import opt
from repro.configs.base import ModelConfig
from repro.models.layers import (
    apply_rope, compute_dtype, dense_init, rms_norm_simple, yarn_freqs,
    yarn_mscale)
from repro.sharding import shard

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Param init
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, kv_input_dim: Optional[int] = None):
    """GQA projection params. ``kv_input_dim`` != None -> cross-attention
    (k/v projected from a different stream, e.g. image/audio embeddings)."""
    d, hd = cfg.d_model, cfg.head_dim
    h, k = cfg.num_heads, cfg.num_kv_heads
    dkv = kv_input_dim or d
    dt = compute_dtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dt),
        "wk": dense_init(ks[1], (dkv, k * hd), dt),
        "wv": dense_init(ks[2], (dkv, k * hd), dt),
        "wo": dense_init(ks[3], (h * hd, d), dt),
    }
    if cfg.use_bias:
        p.update(bq=jnp.zeros((h * hd,), dt), bk=jnp.zeros((k * hd,), dt),
                 bv=jnp.zeros((k * hd,), dt), bo=jnp.zeros((d,), dt))
    if cfg.use_qk_norm:
        p["qnorm"] = jnp.ones((hd,), jnp.float32)
        p["knorm"] = jnp.ones((hd,), jnp.float32)
    return p


def project_qkv(p, x, cfg: ModelConfig, kv_x=None, positions=None,
                rope: bool = True):
    """Project and (optionally) rotate q/k/v. Returns (B,S,H,hd), 2x(B,Skv,K,hd)."""
    B, S, _ = x.shape
    kv_x = x if kv_x is None else kv_x
    Skv = kv_x.shape[1]
    h, k, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"] + p.get("bq", 0.0)).reshape(B, S, h, hd)
    kk = (kv_x @ p["wk"] + p.get("bk", 0.0)).reshape(B, Skv, k, hd)
    vv = (kv_x @ p["wv"] + p.get("bv", 0.0)).reshape(B, Skv, k, hd)
    if cfg.use_qk_norm:
        q = rms_norm_simple(q, p["qnorm"])
        kk = rms_norm_simple(kk, p["knorm"])
    if rope and cfg.rope_theta > 0:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        kk = apply_rope(kk, positions, cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    kk = shard(kk, "batch", None, None, None)
    vv = shard(vv, "batch", None, None, None)
    return q, kk, vv


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------


def make_mask(S: int, Skv: int, *, causal: bool, window: Optional[int] = None,
              q_offset=0, kv_lengths=None, batch: Optional[int] = None):
    """(1|B, 1, S, Skv) boolean mask; True = attend."""
    qi = jnp.arange(S)[:, None] + q_offset          # query absolute positions
    ki = jnp.arange(Skv)[None, :]
    m = jnp.ones((S, Skv), bool)
    if causal:
        m &= ki <= qi
    if window is not None:
        m &= ki > qi - window
    m = m[None, None]                                # (1,1,S,Skv)
    if kv_lengths is not None:                       # right-padded rows
        valid = ki[0] < kv_lengths[:, None]          # (B,Skv)
        m = m & valid[:, None, None, :]
    return m


# ---------------------------------------------------------------------------
# Core attention (pure jnp oracle path)
# ---------------------------------------------------------------------------


def gqa_attention(q, k, v, mask=None, logit_cap: Optional[float] = None):
    """q (B,S,H,hd), k/v (B,Skv,K,hd) -> (B,S,H,hd). fp32 softmax."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    q = q.reshape(B, S, K, G, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    if opt.enabled("attn_dtype"):
        # keep K/V in model dtype; accumulate in f32 on the MXU — avoids
        # materializing f32 copies of K/V (or the whole decode cache).
        scores = jnp.einsum("bskgh,btkh->bkgst", q, k,
                            preferred_element_type=jnp.float32) * scale
    else:
        scores = jnp.einsum("bskgh,btkh->bkgst", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale   # (B,K,G,S,Skv)
    if logit_cap:
        scores = logit_cap * jnp.tanh(scores / logit_cap)
    if mask is not None:
        scores = jnp.where(mask[:, :, None], scores, NEG_INF)  # (B|1,1,1,S,Skv)
    probs = jax.nn.softmax(scores, axis=-1)
    if opt.enabled("attn_dtype"):
        out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def attention_block(p, x, cfg: ModelConfig, *, positions=None, kv_x=None,
                    causal: bool = True, window: Optional[int] = None,
                    kv_lengths=None, rope: bool = True):
    """Full-sequence attention (train / prefill / cross). Returns (B,S,D).

    With ``pallas_attn`` enabled (and a self-attention call whose shapes
    tile), the blocked flash kernel replaces the materialized-scores jnp
    path — the TPU production prefill."""
    B, S, _ = x.shape
    q, k, v = project_qkv(p, x, cfg, kv_x=kv_x, positions=positions, rope=rope)
    Skv = k.shape[1]
    use_kernel = (opt.enabled("pallas_attn") and kv_x is None
                  and cfg.head_dim % 8 == 0 and S >= 16)
    if use_kernel:
        from repro.kernels.flash_attention import flash_attention
        out = flash_attention(q, k, v, causal=causal, window=window,
                              lengths=kv_lengths)
    else:
        mask = None
        if causal or window is not None or kv_lengths is not None:
            mask = make_mask(S, Skv, causal=causal, window=window,
                             kv_lengths=kv_lengths)
        out = gqa_attention(q, k, v, mask)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"] + p.get("bo", 0.0)


# ---------------------------------------------------------------------------
# KV cache ops (decode)
# ---------------------------------------------------------------------------


def cache_dtype(cfg: ModelConfig):
    """float8_e4m3 KV cache halves decode HBM traffic (opt ``kv_cache_f8``)."""
    if opt.enabled("kv_cache_f8") and cfg.dtype == "bfloat16":
        return jnp.float8_e4m3fn
    return compute_dtype(cfg)


def init_kv_cache(num_layers: int, batch: int, max_len: int, cfg: ModelConfig,
                  dtype=None):
    """Stacked dense cache: ``k``/``v`` are (L, B, Smax, K*hd).  With the
    heads flattened into the minor dimension the stored layout is the one
    the decode step reads whatever ``head_dim`` is (80 alone would pad to
    128 lanes, and the layer loop would relayout each layer's slice)."""
    dt = dtype or cache_dtype(cfg)
    shape = (num_layers, batch, max_len, cfg.num_kv_heads * cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def cache_write(cache_k, cache_v, new_k, new_v, lengths):
    """Write one token per row at position lengths[b].

    cache_k/v: (B, Smax, K, hd); new_k/v: (B, 1, K, hd); lengths: (B,)"""
    B = cache_k.shape[0]
    rows = jnp.arange(B)
    ck = cache_k.at[rows, lengths].set(new_k[:, 0].astype(cache_k.dtype))
    cv = cache_v.at[rows, lengths].set(new_v[:, 0].astype(cache_v.dtype))
    return ck, cv


def stacked_write(cache, layer, positions, new):
    """Write new tokens into layer ``layer`` of a stacked cache, in place:
    ``leaf[layer, b, positions[b, i]] = new[b, i]`` for every leaf.

    cache leaves (L, B, Smax, C); positions (B, W); new leaves (B, W, ...)
    holding C values a token.  Positions at or past Smax drop (jax scatter
    default), as the dense cache has always done at its max_len boundary."""
    B, W = positions.shape
    rows = jnp.arange(B)[:, None]
    return {name: leaf.at[layer, rows, positions].set(
                new[name].reshape(B, W, -1).astype(leaf.dtype))
            for name, leaf in cache.items()}


def layer_kv(cache, layer, cfg: ModelConfig):
    """Layer ``layer`` of the stacked dense cache as (B, Smax, K, hd)."""
    _, B, Smax, _ = cache["k"].shape
    shape = (B, Smax, cfg.num_kv_heads, cfg.head_dim)
    return cache["k"][layer].reshape(shape), cache["v"][layer].reshape(shape)


def ring_write(cache_k, cache_v, new_k, new_v, lengths, window: int):
    """Ring-buffer write: token at position L lands in slot L % window.

    A ring cache of size ``window`` holds exactly the last ``window``
    tokens — the sliding-window serving cache is O(window), not O(seq)."""
    B = cache_k.shape[0]
    rows = jnp.arange(B)
    slots = lengths % window
    ck = cache_k.at[rows, slots].set(new_k[:, 0].astype(cache_k.dtype))
    cv = cache_v.at[rows, slots].set(new_v[:, 0].astype(cache_v.dtype))
    return ck, cv


def ring_lengths(lengths, window: int):
    """#valid ring slots after the current token was written."""
    return jnp.minimum(lengths + 1, window)


def ring_fill(k_full, lengths, window: int):
    """Pack the last ``window`` positions of a (B, S, ...) tensor into ring
    order: slot s holds the newest token t < L with t %% window == s."""
    B, S = k_full.shape[:2]
    s = jnp.arange(window)[None, :]
    L = lengths[:, None]
    t = L - 1 - jnp.mod(L - 1 - s, window)          # (B, W), may be negative
    t = jnp.clip(t, 0, S - 1)
    idx = t.reshape(B, window, *([1] * (k_full.ndim - 2)))
    return jnp.take_along_axis(k_full, idx, axis=1)


def decode_attention_ref(q, cache_k, cache_v, lengths, *,
                         window: Optional[int] = None):
    """One-token attention against the cache (pure-jnp flash-decode oracle).

    q: (B, H, hd); cache_k/v: (B, Smax, K, hd); lengths: (B,) = #valid
    (including the token written this step). Returns (B, H, hd)."""
    B, H, hd = q.shape
    K = cache_k.shape[2]
    G = H // K
    Smax = cache_k.shape[1]
    if cache_k.dtype == jnp.float8_e4m3fn:       # dequantize for the MXU
        cache_k = cache_k.astype(jnp.bfloat16)
        cache_v = cache_v.astype(jnp.bfloat16)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    if opt.enabled("attn_dtype"):
        qr = q.reshape(B, K, G, hd)
        scores = jnp.einsum("bkgh,btkh->bkgt", qr, cache_k,
                            preferred_element_type=jnp.float32) * scale
    else:
        qf = q.reshape(B, K, G, hd).astype(jnp.float32)
        scores = jnp.einsum("bkgh,btkh->bkgt", qf,
                            cache_k.astype(jnp.float32)) * scale
    pos = jnp.arange(Smax)[None, :]
    valid = pos < lengths[:, None]
    if window is not None:
        valid &= pos > (lengths[:, None] - 1 - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if opt.enabled("attn_dtype"):
        out = jnp.einsum("bkgt,btkh->bkgh", probs.astype(cache_v.dtype),
                         cache_v, preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkgt,btkh->bkgh", probs,
                         cache_v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def decode_attn_block(p, x1, layer_cache_k, layer_cache_v, lengths,
                      cfg: ModelConfig, *, window: Optional[int] = None,
                      rope: bool = True):
    """Single-token self-attention with cache read-modify-write.

    If the cache is ring-sized (Smax == window < full context, the
    ``ring_cache`` optimization), writes wrap and the window mask is
    implicit.  x1: (B, 1, D). Returns (out (B,1,D), new_k, new_v)."""
    B = x1.shape[0]
    positions = lengths[:, None]                       # this token's position
    q, k, v = project_qkv(p, x1, cfg, positions=positions, rope=rope)
    Smax = layer_cache_k.shape[1]
    if window is not None and Smax <= window:          # ring mode
        ck, cv = ring_write(layer_cache_k, layer_cache_v, k, v, lengths,
                            Smax)
        out = decode_attention_ref(q[:, 0], ck, cv,
                                   ring_lengths(lengths, Smax))
    else:
        ck, cv = cache_write(layer_cache_k, layer_cache_v, k, v, lengths)
        out = decode_attention_ref(q[:, 0], ck, cv, lengths + 1,
                                   window=window)
    out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"] + p.get("bo", 0.0), ck, cv


def stacked_decode_attn_block(p, x1, cache, layer, lengths,
                              cfg: ModelConfig, *,
                              window: Optional[int] = None):
    """Single-token self-attention against layer ``layer`` of the stacked
    dense cache (``init_kv_cache``), updated in place.

    The token's K/V land at ``[layer, b, lengths[b]]`` (ring mode, Smax <=
    window: at ``lengths % Smax``, as ``ring_write``); the layer is then
    read through the same ``decode_attention_ref`` as ``decode_attn_block``
    under the same mask.  x1: (B, 1, D).  Returns (out (B,1,D), cache)."""
    B = x1.shape[0]
    q, k, v = project_qkv(p, x1, cfg, positions=lengths[:, None])
    Smax = cache["k"].shape[2]
    ring = window is not None and Smax <= window
    pos = lengths % Smax if ring else lengths
    cache = stacked_write(cache, layer, pos[:, None], {"k": k, "v": v})
    ck, cv = layer_kv(cache, layer, cfg)
    if ring:
        out = decode_attention_ref(q[:, 0], ck, cv,
                                   ring_lengths(lengths, Smax))
    else:
        out = decode_attention_ref(q[:, 0], ck, cv, lengths + 1,
                                   window=window)
    out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"] + p.get("bo", 0.0), cache


def cross_decode_attn_block(p, x1, kv_k, kv_v, cfg: ModelConfig,
                            kv_lengths=None):
    """Single-token cross-attention against a FIXED KV set (image/audio).

    kv_k/v: (B, T, K, hd) precomputed at prefill."""
    B = x1.shape[0]
    h, hd = cfg.num_heads, cfg.head_dim
    q = (x1 @ p["wq"] + p.get("bq", 0.0)).reshape(B, 1, h, hd)
    if cfg.use_qk_norm:
        q = rms_norm_simple(q, p["qnorm"])
    T = kv_k.shape[1]
    lengths = kv_lengths if kv_lengths is not None else jnp.full((B,), T)
    out = decode_attention_ref(q[:, 0], kv_k, kv_v, lengths)
    out = out.reshape(B, 1, h * hd)
    return out @ p["wo"] + p.get("bo", 0.0)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    dt = compute_dtype(cfg)
    ks = jax.random.split(key, 6)
    qh = m.rope_head_dim + m.nope_head_dim
    return {
        "q_a": dense_init(ks[0], (d, m.q_lora_rank), dt),
        "q_a_scale": jnp.ones((m.q_lora_rank,), jnp.float32),
        "q_b": dense_init(ks[1], (m.q_lora_rank, H * qh), dt),
        "kv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.rope_head_dim), dt),
        "kv_a_scale": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "kv_b": dense_init(
            ks[3], (m.kv_lora_rank, H * (m.nope_head_dim + m.v_head_dim)), dt),
        "wo": dense_init(ks[4], (H * m.v_head_dim, d), dt),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(nope + rope), times mscale(mscale_all_dim)^2 under YaRN."""
    m = cfg.mla
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def _mla_rope(x, positions, cfg):
    """Rotary positions on MLA's rope dims, YaRN-scaled where configured:
    cos and sin carry mscale(mscale) / mscale(mscale_all_dim)."""
    ys = cfg.rope_scaling
    if ys is None:
        return apply_rope(x, positions, cfg.rope_theta)
    out = apply_rope(x, positions, cfg.rope_theta,
                     freqs=yarn_freqs(x.shape[-1], cfg.rope_theta, ys))
    ratio = (yarn_mscale(ys.factor, ys.mscale)
             / yarn_mscale(ys.factor, ys.mscale_all_dim))
    return out if ratio == 1.0 else (out * ratio).astype(out.dtype)


def _mla_q(p, x, cfg, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_lat = rms_norm_simple(x @ p["q_a"], p["q_a_scale"])
    q = (q_lat @ p["q_b"]).reshape(B, S, H, m.rope_head_dim + m.nope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = _mla_rope(q_rope, positions, cfg)
    return q_nope, q_rope


def _mla_ckv(p, x, cfg, positions):
    m = cfg.mla
    kv = x @ p["kv_a"]                                   # (B,S,kvr+rope)
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm_simple(c_kv, p["kv_a_scale"])
    k_rope = _mla_rope(k_rope[:, :, None, :], positions, cfg)[:, :, 0]
    return c_kv, k_rope


# prefill attention runs in blocks of this many queries, so its scores are
# (B, H, MLA_Q_BLOCK, S) and not (B, H, S, S): 128 heads at S = 2048 would
# otherwise hold 2 GiB of float32 scores a layer, and as much again in
# probabilities
MLA_Q_BLOCK = 256


def _mla_attend_block(q_nope, q_rope, k_nope, k_rope, v, kv_lengths, scale,
                      q_offset):
    """Causal attention of a block of queries starting at ``q_offset``
    against every key: q (B, bq, H, .), k_nope/v (B, S, H, .), k_rope
    (B, S, r).  Returns (B, bq, H, v) float32."""
    bq, S = q_nope.shape[1], k_nope.shape[1]
    if opt.enabled("attn_dtype"):
        scores = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshr,btr->bhst", q_rope, k_rope,
                               preferred_element_type=jnp.float32)) * scale
    else:
        f32 = jnp.float32
        scores = (jnp.einsum("bshn,bthn->bhst", q_nope.astype(f32),
                             k_nope.astype(f32))
                  + jnp.einsum("bshr,btr->bhst", q_rope.astype(f32),
                               k_rope.astype(f32))) * scale
    mask = make_mask(bq, S, causal=True, q_offset=q_offset,
                     kv_lengths=kv_lengths)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if opt.enabled("attn_dtype"):
        return jnp.einsum("bhst,bthv->bshv", probs.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bhst,bthv->bshv", probs, v.astype(jnp.float32))


def mla_prefill_block(p, x, cfg: ModelConfig, *, positions=None,
                      kv_lengths=None):
    """Full-sequence MLA (train/prefill): materializes per-head k, v and
    attends in blocks of ``MLA_Q_BLOCK`` queries.  Returns (out (B,S,D),
    c_kv (B,S,kvr), k_rope (B,S,rope)): the latent the decode cache
    holds, computed once."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_ckv(p, x, cfg, positions)
    kvb = (c_kv @ p["kv_b"]).reshape(B, S, H, m.nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.nope_head_dim], axis=-1)
    scale = mla_softmax_scale(cfg)
    with jax.named_scope("mla.attend"):
        if S <= MLA_Q_BLOCK or S % MLA_Q_BLOCK:
            out = _mla_attend_block(q_nope, q_rope, k_nope, k_rope, v,
                                    kv_lengths, scale, 0)
        else:
            nb = S // MLA_Q_BLOCK

            def blocks(t):                  # (B, S, ...) -> (nb, B, bq, ...)
                t = t.reshape(B, nb, MLA_Q_BLOCK, *t.shape[2:])
                return jnp.moveaxis(t, 1, 0)

            out = jax.lax.map(
                lambda a: _mla_attend_block(a[0], a[1], k_nope, k_rope, v,
                                            kv_lengths, scale, a[2]),
                (blocks(q_nope), blocks(q_rope),
                 jnp.arange(nb) * MLA_Q_BLOCK))
            out = jnp.moveaxis(out, 0, 1)   # (B, nb, bq, H, v)
    out = out.reshape(B, S, H * m.v_head_dim).astype(x.dtype)
    return out @ p["wo"], c_kv, k_rope


def mla_attention_block(p, x, cfg: ModelConfig, *, positions=None,
                        kv_lengths=None):
    """Full-sequence MLA (train/prefill).  Returns (B,S,D)."""
    return mla_prefill_block(p, x, cfg, positions=positions,
                             kv_lengths=kv_lengths)[0]


def rope_lanes(cfg: ModelConfig) -> int:
    """Width of the stored rope key: ``rope_head_dim`` rounded up to the
    TPU's 128 lanes, the rest zero.  A 64-wide minor dim takes 128 lanes
    in HBM all the same; stated 64 wide, the decode loop's dot wants it
    positions-minor and the whole stacked leaf is relaid out on the way
    into the loop and back, every step."""
    return -(-cfg.mla.rope_head_dim // 128) * 128


def pad_rope(t, cfg: ModelConfig):
    """Zero-pad the last dim of a rope key or query to ``rope_lanes``."""
    extra = rope_lanes(cfg) - t.shape[-1]
    return jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, extra)])


def init_mla_cache(num_layers: int, batch: int, max_len: int,
                   cfg: ModelConfig, dtype=None):
    """Stacked latent cache: ``ckv`` (L, B, Smax, kv_lora) and ``krope``
    (L, B, Smax, rope_lanes), the rotated key zero-padded (``pad_rope``)."""
    m = cfg.mla
    dt = dtype or compute_dtype(cfg)
    return {
        "ckv": jnp.zeros((num_layers, batch, max_len, m.kv_lora_rank), dt),
        "krope": jnp.zeros((num_layers, batch, max_len, rope_lanes(cfg)), dt),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def mla_decode_block(p, x1, cache, layer, lengths, cfg: ModelConfig):
    """Absorbed-matrix MLA decode: attention in the latent (kv_lora) space.

    x1 (B,1,D); cache {"ckv": (L,B,Smax,kvr), "krope": (L,B,Smax,lanes)}
    (``init_mla_cache``), written in place at ``[layer, b, lengths[b]]``.
    Returns (out (B,1,D), cache)."""
    m = cfg.mla
    B = x1.shape[0]
    H = cfg.num_heads
    positions = lengths[:, None]
    q_nope, q_rope = _mla_q(p, x1, cfg, positions)       # (B,1,H,n),(B,1,H,r)
    c_kv, k_rope = _mla_ckv(p, x1, cfg, positions)       # (B,1,kvr),(B,1,r)
    cache = stacked_write(cache, layer, positions,
                          {"ckv": c_kv, "krope": pad_rope(k_rope, cfg)})
    # absorb W_UK into q: q_abs[b,h,c] = sum_n q_nope[b,h,n] * W_UK[c,h,n]
    kvb = p["kv_b"].reshape(m.kv_lora_rank, H, m.nope_head_dim + m.v_head_dim)
    w_uk = kvb[:, :, :m.nope_head_dim]                   # (kvr,H,n)
    w_uv = kvb[:, :, m.nope_head_dim:]                   # (kvr,H,v)
    q_abs = jnp.einsum("bhn,chn->bhc", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))         # (B,H,kvr)
    scale = mla_softmax_scale(cfg)
    with jax.named_scope("mla.attend"):
        out_lat = _mla_absorbed_attend(
            q_abs, pad_rope(q_rope[:, 0], cfg), cache["ckv"][layer],
            cache["krope"][layer], lengths + 1, scale)
    out = jnp.einsum("bhc,chv->bhv", out_lat, w_uv.astype(jnp.float32))
    out = out.reshape(B, 1, H * m.v_head_dim).astype(x1.dtype)
    return out @ p["wo"], cache


def _mla_absorbed_attend(q_abs, q_rope, c_cache, r_cache, valid_len, scale):
    """One query per row against the latent cache, in the latent space:
    q_abs (B,H,kvr), q_rope (B,H,lanes), c_cache (B,Smax,kvr), r_cache
    (B,Smax,lanes); positions below ``valid_len`` (B,) attend.  Returns
    the attended latent (B,H,kvr) float32."""
    if opt.enabled("attn_dtype"):
        scores = (jnp.einsum("bhc,btc->bht", q_abs.astype(c_cache.dtype),
                             c_cache, preferred_element_type=jnp.float32)
                  + jnp.einsum("bhr,btr->bht", q_rope, r_cache,
                               preferred_element_type=jnp.float32)) * scale
    else:
        scores = (jnp.einsum("bhc,btc->bht", q_abs,
                             c_cache.astype(jnp.float32))
                  + jnp.einsum("bhr,btr->bht", q_rope.astype(jnp.float32),
                               r_cache.astype(jnp.float32))) * scale
    Smax = c_cache.shape[1]
    valid = jnp.arange(Smax)[None, :] < valid_len[:, None]
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if opt.enabled("attn_dtype"):
        return jnp.einsum("bht,btc->bhc", probs.astype(c_cache.dtype),
                          c_cache, preferred_element_type=jnp.float32)
    return jnp.einsum("bht,btc->bhc", probs, c_cache.astype(jnp.float32))
