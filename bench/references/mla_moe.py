"""Plain float32 reference of a DeepSeek-V3 decoder at one chip's share of
its experts: multi-head latent attention (MLA) with YaRN rotary scaling,
leading dense SwiGLU layers, then mixture-of-experts layers routed by
sigmoid scores with a selection-only bias over groups of experts, plus a
shared expert; untied output head.

It imports nothing of the program.  Its weights are made from the seed by
the program's init recipe for the file's ``model``: ``PRNGKey(seed)``
split six ways; the embedding from key 0 (truncated normal on [-2, 2]
times 0.02), the head from key 1, the leading dense layers from key 2 and
the expert layers from key 3, each split once per layer.  A layer's key is
split four ways: the attention from key 0, the MLP or experts from key 1.
The attention's key is split six ways into q_a, q_b, kv_a, kv_b and wo
(keys 0-4); an MLP's three ways into gate, up and down; an expert layer's
five ways: the router (float32) from key 0, the held experts' gate, up
and down as (held, in, out) blocks from keys 1-3, the shared expert from
key 4 as an MLP, and the routing bias N(0, 0.05^2) from ``fold_in(router
key, 1)``.  Every matrix is a truncated normal times 1/sqrt(fan_in);
RMSNorm scales are 1.  Matrices are rounded to the stated dtype, as the
model holds them, then computed in float32 at ``highest`` matmul
precision, layer by layer, with the head applied in vocabulary blocks.

The layer equations (arXiv:2412.19437 §2.1; DeepSeek-V3's
``modeling_deepseek.py``):

- MLA, unabsorbed: q = W_qb rms(W_qa h), split per head into 128 "nope"
  and 64 rope dims; [c_kv, k_rope] = W_kva h, c_kv rms-normed; per head
  [k_nope, v] = W_kvb c_kv, materialised; rope on q_rope and on k_rope
  (one key shared by every head) with YaRN frequencies (``yarn_freqs``);
  scores (q_nope k_nope + q_rope k_rope) times 1/sqrt(192) times
  mscale(40, mscale_all_dim)^2; causal softmax; W_o over the heads.  The
  program decodes in the absorbed form (W_uk folded into the query, the
  values read in the latent space), so this checks that too.
- Routing: s = sigmoid(h W_r) in float32; each of 8 groups of 32 experts
  scores the sum of its two best s + b; the best 4 groups are kept, the
  others set to -inf; the top 8 of s + b among the kept experts are
  chosen, and their weights are their s, normalised to sum 1, times 2.5.
- The expert layer's result is, over the chosen experts that are held
  here (experts [0, held)), the sum of w_e down(silu(gate h) * up h),
  plus the shared expert; the absent experts' part is left out, as the
  program leaves it out: this is one chip's share of an expert-parallel
  deployment.

Departures from the published model, each shared with the program:
attention is computed whole on the chip (data-parallel attention; the
report shards it over four chips); the rope pairs are the two halves of
the rope dims, where the published code pairs interleaved dims (a fixed
permutation of W_qb's and W_kva's rope columns, so equivalent under
random weights); excluded groups are masked to -inf rather than 0 (the
published 0 can be chosen only when fewer than top-k kept scores are
positive, which cannot happen with sigmoid scores plus a small bias);
the multi-token prediction layer is not built.

``control=True`` computes every linear layer in the precision below the
configuration's bf16: both operands of each matmul (router and head
included) rounded to float8_e4m3 with a per-tensor scale (``fp8``).

A configuration names this file with ``"reference": "mla_moe"``."""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from references.dense_gqa import NEG, _dtype, _embed, _mat, _rms, fp8

ROUTER_BIAS_STD = 0.05
HEAD_BLOCK = 32768          # vocabulary columns per head block
HEAD_CHUNK = 32             # attention heads scored at a time


def yarn_freqs(dim: int, theta: float, ys: Dict[str, Any]) -> np.ndarray:
    """YaRN inverse frequencies for ``dim`` rotary dims: theta^(-2i/dim)
    extrapolated, those / factor interpolated, blended by the ramp
    clip((i - low) / (high - low), 0, 1) with low = floor(d(beta_fast)),
    high = ceil(d(beta_slow)), d(r) = dim ln(L0 / (2 pi r)) / (2 ln
    theta)."""
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    inter = extra / ys["factor"]

    def d(r):
        return (dim * math.log(ys["original_max_position"] / (2 * math.pi * r))
                / (2 * math.log(theta)))

    low = max(math.floor(d(ys["beta_fast"])), 0)
    high = min(math.ceil(d(ys["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


class Reference:
    """One model's weights-from-seed and its float32 forward."""

    def __init__(self, m: Dict[str, Any], seed: int, control: bool = False):
        self.m = m
        self.dt = _dtype(m["dtype"])
        self.control = control
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        self.keys = ks
        self.n_dense = m["moe"]["first_k_dense"]
        self.dense_keys = jax.random.split(ks[2], self.n_dense)
        self.moe_keys = jax.random.split(ks[3], m["num_layers"] - self.n_dense)

    # --- weights -----------------------------------------------------------

    def embed(self):
        m = self.m
        return _matj(self.keys[0], (m["vocab_size"], m["d_model"]), 0.02,
                     self.dt, False)

    def head(self):
        """(d, V) float32, applied in vocabulary blocks."""
        m = self.m
        return _matj(self.keys[1], (m["d_model"], m["vocab_size"]),
                     1.0 / np.sqrt(m["d_model"]), self.dt, self.control)

    def layer(self, i: int) -> Dict[str, Any]:
        """Layer ``i``'s weights in float32: a dense layer for i below
        ``first_k_dense``, an expert layer after."""
        m, a, e = self.m, self.m["mla"], self.m["moe"]
        dense = i < self.n_dense
        key = (self.dense_keys[i] if dense
               else self.moe_keys[i - self.n_dense])
        shapes = (m["d_model"], m["num_heads"], a["q_lora_rank"],
                  a["kv_lora_rank"], a["rope_head_dim"], a["nope_head_dim"],
                  a["v_head_dim"])
        ff = e["d_ff_dense"] if dense else None
        moe = None if dense else (e["num_experts"], e["experts_held"]
                                  or e["num_experts"], e["d_ff_expert"],
                                  e["num_shared_experts"])
        return _layer_weights(key, shapes, ff, moe, self.dt, self.control)

    # --- forward -------------------------------------------------------------

    def logits(self, blocks, positions, cols: Optional[int] = None
               ) -> List[List[np.ndarray]]:
        """Float32 logits of right-padded rows at chosen positions.

        ``blocks`` is a list of (tokens (B, S), lengths (B,)); each layer's
        weights are made once and applied to every block.  ``positions``
        gives, per block and row, the positions to read.  Returns, per
        block and row, an array (positions, vocab) — the first ``cols``
        entries of the vocabulary when set."""
        m = self.m
        eps = float(m["norm_eps"])
        a = m["mla"]
        rope = yarn_freqs(a["rope_head_dim"], float(m["rope_theta"]),
                          m["rope_scaling"])
        ys = m["rope_scaling"]
        scale = ((a["nope_head_dim"] + a["rope_head_dim"]) ** -0.5
                 * mscale(ys["factor"], ys["mscale_all_dim"]) ** 2)
        rope_gain = (mscale(ys["factor"], ys["mscale"])
                     / mscale(ys["factor"], ys["mscale_all_dim"]))
        route = _route_args(m["moe"])
        dims = (m["num_heads"], a["nope_head_dim"], a["v_head_dim"])
        with jax.default_matmul_precision("highest"):
            table = self.embed()
            xs = [_embed(table, jnp.asarray(t)) for t, _ in blocks]
            del table
            lens = [jnp.asarray(n, jnp.int32) for _, n in blocks]
            for i in range(m["num_layers"]):
                w = self.layer(i)
                xs = [_block(w, x, n, jnp.asarray(rope), rope_gain, scale,
                             eps, route, dims, self.control)
                      for x, n in zip(xs, lens)]
                del w
            head = self.head()
            V = head.shape[1] if cols is None else cols
            out = []
            for x, pos in zip(xs, positions):
                # pad every row's positions to the block's length: one
                # compiled projection per block shape
                width = max(len(p) for p in pos)
                idx = np.zeros((len(pos), x.shape[1] if width > 1 else 1),
                               np.int32)
                for b, p in enumerate(pos):
                    idx[b, :len(p)] = p
                h = _final(x, jnp.asarray(idx), eps, self.control)
                got = np.concatenate(
                    [np.asarray(_project(h, head[:, v0:min(v0 + HEAD_BLOCK,
                                                           V)]))
                     for v0 in range(0, V, HEAD_BLOCK)], axis=-1)
                out.append([got[b, :len(p)] for b, p in enumerate(pos)])
            return out


def _route_args(e: Dict[str, Any]):
    return (e["top_k"], e["n_group"], e["topk_group"],
            float(e["routed_scaling"]), bool(e["norm_topk_prob"]))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _matj(key, shape, std, dt, control):
    """One matrix, generated in one fused program (an eager truncated
    normal of the 129,280 x 7,168 embedding holds several float32 copies
    of it at once); float8-rounded for the control."""
    w = _mat(key, shape, std, dt)
    return fp8(w) if control else w


def _layer_weights(key, shapes, ff, moe, dt, control):
    """A layer's float32 weights by the program's recipe, one matrix at a
    time."""
    d, H, ql, kvr, rd, nd, vd = shapes
    ka, km = jax.random.split(key, 4)[:2]
    kq_a, kq_b, kkv_a, kkv_b, ko = jax.random.split(ka, 6)[:5]
    sd = 1.0 / np.sqrt(d)
    w = {"q_a": _matj(kq_a, (d, ql), sd, dt, control),
         "q_b": _matj(kq_b, (ql, H * (nd + rd)), 1.0 / np.sqrt(ql), dt,
                      control),
         "kv_a": _matj(kkv_a, (d, kvr + rd), sd, dt, control),
         "kv_b": _matj(kkv_b, (kvr, H * (nd + vd)), 1.0 / np.sqrt(kvr), dt,
                       control),
         "wo": _matj(ko, (H * vd, d), 1.0 / np.sqrt(H * vd), dt, control)}
    if moe is None:
        w.update(_mlp(km, d, ff, dt, control, ""))
        return w
    E, held, fe, n_shared = moe
    kr, kg, ku, kd, ks = jax.random.split(km, 5)
    w["router"] = _matj(kr, (d, E), sd, jnp.float32, control)
    # op by op, as the program's init draws it: a jitted normal can differ
    # from an eager one in the last bit
    w["router_bias"] = ROUTER_BIAS_STD * jax.random.normal(
        jax.random.fold_in(kr, 1), (E,), jnp.float32)
    w["we_gate"] = _matj(kg, (held, d, fe), sd, dt, control)
    w["we_up"] = _matj(ku, (held, d, fe), sd, dt, control)
    w["we_down"] = _matj(kd, (held, fe, d), 1.0 / np.sqrt(fe), dt, control)
    w.update(_mlp(ks, d, fe * n_shared, dt, control, "s_"))
    return w


def _mlp(key, d, ff, dt, control, prefix):
    g, u, dn = jax.random.split(key, 3)
    return {prefix + "gate": _matj(g, (d, ff), 1.0 / np.sqrt(d), dt, control),
            prefix + "up": _matj(u, (d, ff), 1.0 / np.sqrt(d), dt, control),
            prefix + "down": _matj(dn, (ff, d), 1.0 / np.sqrt(ff), dt,
                                   control)}


def _rope(x, pos, freqs, gain):
    """Rotary positions on the two halves of the last dim."""
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    if x.ndim == 4:                                   # (B, S, H, r)
        cos, sin = cos[:, :, None], sin[:, :, None]
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def route(logits, bias, top_k, n_group, topk_group, scaling, norm):
    """Weights (T, E) of each token's chosen experts (zero elsewhere)."""
    T, E = logits.shape
    s = jax.nn.sigmoid(logits)
    b = s + bias
    g = b.reshape(T, n_group, E // n_group)
    top2 = jnp.sort(g, axis=-1)[..., -2:].sum(-1)               # (T, G)
    kth = jnp.sort(top2, axis=-1)[:, n_group - topk_group][:, None]
    kept = jnp.repeat(top2 >= kth, E // n_group, axis=1)
    b = jnp.where(kept, b, -jnp.inf)
    cut = jnp.sort(b, axis=-1)[:, E - top_k][:, None]
    chosen = b >= cut
    w = jnp.where(chosen, s, 0.0)
    if norm:
        w = w / w.sum(-1, keepdims=True)
    return w * scaling


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _block(w, x, lengths, freqs, gain, scale, eps, route_args, dims,
           control):
    """One layer over right-padded rows x (B, S, d); ``dims`` is (heads,
    nope dims, value dims)."""
    B, S, d = x.shape
    H, nd, vd = dims
    lo = fp8 if control else (lambda t: t)     # left operand of a matmul
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = _rms(x, eps)
    kvr = w["kv_b"].shape[0]
    q = _rms(lo(h) @ w["q_a"], 1e-6)
    q = (lo(q) @ w["q_b"]).reshape(B, S, H, -1)
    q_nope, q_rope = q[..., :nd], _rope(q[..., nd:], pos, freqs, gain)
    kv = lo(h) @ w["kv_a"]
    c_kv = _rms(kv[..., :kvr], 1e-6)
    k_rope = _rope(kv[..., kvr:], pos, freqs, gain)          # (B, S, r)
    kvb = (lo(c_kv) @ w["kv_b"]).reshape(B, S, H, nd + vd)
    k_nope, v = kvb[..., :nd], kvb[..., nd:]               # per head
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    ok = (ki <= qi)[None, None] & (ki < lengths[:, None, None, None])

    def heads(lo_h, hi_h):
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, :, lo_h:hi_h],
                        k_nope[:, :, lo_h:hi_h])
             + jnp.einsum("bqhr,bkr->bhqk", q_rope[:, :, lo_h:hi_h],
                          k_rope)) * scale
        p = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :, lo_h:hi_h])

    att = jnp.concatenate([heads(c, min(c + HEAD_CHUNK, H))
                           for c in range(0, H, HEAD_CHUNK)], axis=2)
    x = x + lo(att.reshape(B, S, H * vd)) @ w["wo"]
    h = lo(_rms(x, eps))
    if "router" not in w:                                   # dense layer
        return x + lo(jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]
    h2 = h.reshape(B * S, d)
    cw = route(h2 @ w["router"], w["router_bias"], *route_args)  # (T, E)
    y = lo(jax.nn.silu(h2 @ w["s_gate"]) * (h2 @ w["s_up"])) @ w["s_down"]
    for e in range(w["we_gate"].shape[0]):       # the experts held here
        ye = lo(jax.nn.silu(h2 @ w["we_gate"][e]) * (h2 @ w["we_up"][e])) \
            @ w["we_down"][e]
        y = y + cw[:, e:e + 1] * ye
    return x + y.reshape(B, S, d)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _final(x, idx, eps, control):
    h = _rms(jnp.take_along_axis(x, idx[:, :, None], axis=1), eps)
    return fp8(h) if control else h


@jax.jit
def _project(h, head_block):
    return h @ head_block
