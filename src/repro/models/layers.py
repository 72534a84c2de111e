"""Common layers: norms, rotary embeddings, MLPs, initializers.

Pure JAX, params-as-pytrees. Norm statistics are computed in float32
regardless of the compute dtype; matmuls run in the config dtype.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, in_axis: int = -2):
    """Truncated-normal fan-in init (stddev = 1/sqrt(fan_in))."""
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * 0.02).astype(dtype)


def stack_init(key, num: int, init_fn, *args, **kwargs):
    """vmap an init over a leading layer-stack dimension."""
    keys = jax.random.split(key, num)
    return jax.vmap(lambda k: init_fn(k, *args, **kwargs))(keys)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm_kind == "layernorm":
        p["nbias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p, x, cfg: ModelConfig, eps: Optional[float] = None):
    eps = eps or cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"] + p.get("nbias", 0.0)
    else:  # rmsnorm
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


def rms_norm_simple(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def group_norm(x, scale, bias, num_groups: int, eps: float = 1e-5):
    """GroupNorm over the channel dim (rwkv6 per-head output norm)."""
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, num_groups, d // num_groups)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y.reshape(*lead, d)
    return (y * scale + bias).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, ys) -> np.ndarray:
    """YaRN rotary frequencies (``DeepseekV3YarnRotaryEmbedding``) for a
    rotary dim ``dim`` under ``ys`` (a ``YarnConfig``): the extrapolated
    theta^(-2i/dim) and the interpolated ones divided by ``factor``,
    blended by a ramp over the dims between the correction dims of
    beta_fast and beta_slow, d(r) = dim ln(L / (2 pi r)) / (2 ln theta)
    with L the original context."""
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / (theta ** pos)
    inter = 1.0 / (ys.factor * theta ** pos)

    def corr(rot):
        return (dim * math.log(ys.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(ys.beta_fast)), 0)
    high = min(math.ceil(corr(ys.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def apply_rope(x, positions, theta: float, freqs=None):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32.
    ``freqs`` (head_dim/2,) replaces theta's plain frequencies (YaRN)."""
    if theta <= 0.0:
        return x
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)
    freqs = jnp.asarray(freqs)                            # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                   # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(num: int, d: int):
    """Whisper-style sinusoidal embeddings (num, d)."""
    pos = np.arange(num)[:, None]
    dim = np.arange(d // 2)[None, :]
    inv = 1.0 / (10000 ** (dim / max(d // 2 - 1, 1)))
    ang = pos * inv
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), jnp.float32)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None, d: Optional[int] = None):
    d = d or cfg.d_model
    ff = d_ff or cfg.d_ff
    dt = compute_dtype(cfg)
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        p = {
            "w_gate": dense_init(ks[0], (d, ff), dt),
            "w_up": dense_init(ks[1], (d, ff), dt),
            "w_down": dense_init(ks[2], (ff, d), dt),
        }
    else:
        p = {
            "w_up": dense_init(ks[1], (d, ff), dt),
            "w_down": dense_init(ks[2], (ff, d), dt),
        }
    if cfg.use_bias:
        p["b_up"] = jnp.zeros((ff,), dt)
        p["b_down"] = jnp.zeros((d,), dt)
    return p


def apply_mlp(p, x, cfg: ModelConfig):
    from repro.sharding import shard
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif cfg.act == "relu_sq":
        h = jnp.square(jax.nn.relu(x @ p["w_up"] + p.get("b_up", 0.0)))
    else:  # gelu
        h = jax.nn.gelu(x @ p["w_up"] + p.get("b_up", 0.0))
    h = shard(h, *((None,) * (h.ndim - 1)), "ff")
    return h @ p["w_down"] + p.get("b_down", 0.0)


def cross_entropy_loss(logits, labels, mask=None):
    """Mean masked token cross-entropy; logits in fp32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_cross_entropy(h, head, labels, mask=None, chunk: int = 16384):
    """Cross-entropy WITHOUT materializing the (B, S, V) logits tensor.

    Scans vocab chunks with an online logsumexp (each iteration touches
    only (B, S, chunk) — for command-r's 256k vocab this removes the
    single biggest train-step activation).  The body is checkpointed so
    backward re-materializes one chunk at a time too.

    h: (B, S, D); head: (D, V); labels: (B, S) -> scalar mean CE.
    """
    B, S, D = h.shape
    V = head.shape[1]
    nc = -(-V // chunk)
    Vp = nc * chunk
    if Vp != V:
        head = jnp.pad(head, ((0, 0), (0, Vp - V)))
    hf = h.astype(jnp.float32)

    def body(carry, i):
        m, s, gold = carry
        wc = jax.lax.dynamic_slice_in_dim(head, i * chunk, chunk, axis=1)
        logits_c = hf @ wc.astype(jnp.float32)            # (B, S, chunk)
        # mask padded vocab entries out of the logsumexp
        col = i * chunk + jnp.arange(chunk)
        logits_c = jnp.where(col[None, None, :] < V, logits_c, -1e30)
        m_new = jnp.maximum(m, logits_c.max(axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(
            logits_c - m_new[..., None]).sum(axis=-1)
        # gold logit if this row's label falls in the chunk
        in_chunk = (labels >= i * chunk) & (labels < (i + 1) * chunk)
        idx = jnp.clip(labels - i * chunk, 0, chunk - 1)
        g = jnp.take_along_axis(logits_c, idx[..., None], axis=-1)[..., 0]
        gold = jnp.where(in_chunk, g, gold)
        return (m_new, s, gold), None

    init = (jnp.full((B, S), -1e30, jnp.float32),
            jnp.zeros((B, S), jnp.float32),
            jnp.zeros((B, S), jnp.float32))
    (m, s, gold), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), init, jnp.arange(nc))
    nll = (m + jnp.log(s)) - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
