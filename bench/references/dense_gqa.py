"""Plain float32 reference of a dense grouped-query-attention decoder
(the Llama/Mistral block: RMSNorm, rotary positions on the two halves of
each head, causal softmax attention with an optional sliding window,
SwiGLU MLP, untied output head).

It imports nothing of the program.  Its weights are made from the seed by
the published recipe the configuration file names (``init``): for each
model, ``PRNGKey(seed)`` split six ways; the embedding from key 0
(truncated normal on [-2, 2] times 0.02), the head from key 1, the layers
from key 2 split once per layer; each layer's key split four ways, the
attention from key 0 (split into q, k, v, o) and the MLP from key 1
(split into gate, up, down); every matrix a truncated normal times
1/sqrt(fan_in); RMSNorm scales 1.  Matrices are rounded to the stated
dtype, as the model holds them, then computed in float32 at
``highest`` matmul precision.  It runs layer by layer, so only one layer's
weights are on the device at a time.

``control=True`` computes every linear layer in the precision below the
configuration's bf16: both operands of each weight matmul (and of the
head) rounded to float8_e4m3 with a per-tensor scale, accumulated in
float32 (see ``fp8``).

A configuration names this file with ``"reference": "dense_gqa"``.  A
reference for another architecture is a file of its own beside it that
may reuse these helpers (``from references.dense_gqa import fp8, _mat,
_rms, _rope, attention``)."""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def _mat(key, shape, std, dtype):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std
    return w.astype(dtype).astype(jnp.float32)


def fp8(x):
    """Control precision: a tensor stored as float8_e4m3 with one scale
    per tensor (its largest magnitude maps to 448), read back in float32."""
    s = jnp.max(jnp.abs(x)) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


class Reference:
    """One model's weights-from-seed and its float32 forward."""

    def __init__(self, m: Dict[str, Any], seed: int, control: bool = False):
        self.m = m
        self.dt = _dtype(m["dtype"])
        self.control = control
        self.round = fp8 if control else None
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        self.keys = ks
        self.layer_keys = jax.random.split(ks[2], m["num_layers"])

    # --- weights -----------------------------------------------------------

    def _r(self, w):
        return self.round(w) if self.round is not None else w

    def embed(self):
        m = self.m
        return _mat(self.keys[0], (m["vocab_size"], m["d_model"]), 0.02,
                    self.dt)

    def head(self):
        m = self.m
        return self._r(_mat(self.keys[1], (m["d_model"], m["vocab_size"]),
                            1.0 / np.sqrt(m["d_model"]), self.dt))

    def layer(self, i: int) -> Dict[str, Any]:
        return _layer_weights(self.layer_keys[i], self.m["d_model"],
                              self.m["num_heads"], self.m["num_kv_heads"],
                              self.m["head_dim"], self.m["d_ff"], self.dt,
                              self.round)

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
        with jax.default_matmul_precision("highest"):
            table = self.embed()
            xs = [_embed(table, jnp.asarray(t)) for t, _ in blocks]
            del table
            lens = [jnp.asarray(n, jnp.int32) for _, n in blocks]
            for i in range(m["num_layers"]):
                w = self.layer(i)
                xs = [_block(w, x, n, m["num_heads"], m["num_kv_heads"],
                             m["head_dim"], float(m["rope_theta"]),
                             m.get("sliding_window"), eps, self.control)
                      for x, n in zip(xs, lens)]
                del w
            head = self.head()
            if cols is not None:
                head = head[:, :cols]
            out = []
            for x, pos in zip(xs, positions):
                # pad every row's positions to the block's length: one
                # compiled projection per block shape
                width = max(len(p) for p in pos)
                idx = np.zeros((len(pos), x.shape[1] if width > 1 else 1),
                               np.int32)
                for b, p in enumerate(pos):
                    idx[b, :len(p)] = p
                got = np.asarray(_project(x, jnp.asarray(idx), head, eps,
                                          self.control))
                out.append([got[b, :len(p)] for b, p in enumerate(pos)])
            return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _layer_weights(key, d, H, K, hd, ff, dt, round_fn):
    ka, km = jax.random.split(key, 4)[:2]
    q, k, v, o = jax.random.split(ka, 4)
    g, u, dn = jax.random.split(km, 3)
    r = round_fn if round_fn is not None else (lambda w: w)
    sd, sh, sf = 1.0 / np.sqrt(d), 1.0 / np.sqrt(H * hd), 1.0 / np.sqrt(ff)
    return {"wq": r(_mat(q, (d, H * hd), sd, dt)),
            "wk": r(_mat(k, (d, K * hd), sd, dt)),
            "wv": r(_mat(v, (d, K * hd), sd, dt)),
            "wo": r(_mat(o, (H * hd, d), sh, dt)),
            "w_gate": r(_mat(g, (d, ff), sd, dt)),
            "w_up": r(_mat(u, (d, ff), sd, dt)),
            "w_down": r(_mat(dn, (ff, d), sf, dt))}


@jax.jit
def _embed(table, tokens):
    return table[tokens]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _project(x, idx, head, eps, control):
    h = _rms(jnp.take_along_axis(x, idx[:, :, None], axis=1), eps)
    return (fp8(h) if control else h) @ head


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, :, None].astype(jnp.float32) * inv            # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(q, k, v, lengths, window):
    """Causal softmax attention over right-padded rows, with an optional
    sliding window: q (B, S, H, hd), k and v (B, S, K, hd), query head j
    reading kv head j // (H // K); ``lengths`` (B,) masks the padding.
    Returns (B, S, H * hd)."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    k = jnp.repeat(k, H // K, axis=2)          # query head j reads kv j//G
    v = jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    ok = ki <= qi
    if window is not None:
        ok &= ki > qi - window
    ok = ok[None, None] & (ki < lengths[:, None, None, None])
    s = jnp.where(ok, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * hd)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _block(w, x, lengths, H, K, hd, theta, window, eps, control):
    B, S, d = x.shape
    lo = fp8 if control else (lambda t: t)     # left operand of a matmul
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = lo(_rms(x, eps))
    q = _rope((h @ w["wq"]).reshape(B, S, H, hd), pos, theta)
    k = _rope((h @ w["wk"]).reshape(B, S, K, hd), pos, theta)
    v = (h @ w["wv"]).reshape(B, S, K, hd)
    a = attention(q, k, v, lengths, window)
    x = x + lo(a) @ w["wo"]
    h = lo(_rms(x, eps))
    return x + lo(jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
