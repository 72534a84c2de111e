"""Chunked RWKV-6 WKV recurrence — Pallas TPU kernel.

    S_t = diag(w_t) S_{t-1} + k_t v_t^T ;  y_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

Grid: (batch, heads, num_chunks); the chunk axis is sequential so the
(N, N) state lives in VMEM scratch across chunk steps.  All exponentials
take non-positive arguments (ordered-decay products), so the kernel is
stable regardless of how aggressive the learned data-dependent decay is —
no 1/W division anywhere.

Per-chunk working set (c=32, N=64): the (c,c,N) decay tensor is 256 KiB in
fp32, r/k/v/w tiles are 8 KiB each, state is 16 KiB — well inside VMEM.
The intra-chunk einsums contract on the MXU; chunk length trades VMEM
footprint against serialization (hillclimb knob).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sT_ref, s_s,
            *, chunk: int, nc: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        s_s[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)            # (c, N)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)          # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)               # (N,)
    S = s_s[...]                                   # (N, N) state

    c = r.shape[0]
    L = jnp.cumsum(lw, axis=0)                     # inclusive
    Lprev = L - lw                                 # exclusive
    # intra-chunk interactions: D[t,s,n] = exp(L_{t-1,n} - L_{s,n}), s < t
    D = jnp.exp(Lprev[:, None, :] - L[None, :, :])           # (c,c,N)
    A = jnp.einsum("tn,tsn,sn->ts", r, D, k)                 # (c,c)
    tril = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    A = jnp.where(tril, A, 0.0)
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # diagonal bonus
    y += jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True) * v
    # contribution of the carried state
    y += jax.lax.dot_general(r * jnp.exp(Lprev), S,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)

    # state update: S' = diag(exp(L_c)) S + sum_s (k_s exp(L_c-L_s)) v_s^T
    Lc = L[-1:, :]                                  # (1, N)
    kd = k * jnp.exp(Lc - L)                        # (c, N)
    s_s[...] = jnp.exp(Lc)[0][:, None] * S + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nc - 1)
    def _finish():
        sT_ref[0, 0, :, :] = s_s[...].astype(sT_ref.dtype)


def wkv6_bhtn(r, k, v, logw, u, s0, *, chunk: int = 32,
              interpret: bool = True):
    """r/k/v/logw (B,H,T,N) fp32; u (H,N); s0 (B,H,N,N).

    Returns (y (B,H,T,N), s_T (B,H,N,N)). T must divide by ``chunk``."""
    B, H, T, N = r.shape
    assert T % chunk == 0
    nc = T // chunk
    kern = functools.partial(_kernel, chunk=chunk, nc=nc)
    spec_t = pl.BlockSpec((1, 1, chunk, N), lambda b, h, j: (b, h, j, 0))
    spec_s = pl.BlockSpec((1, 1, N, N), lambda b, h, j: (b, h, 0, 0))
    y, sT = pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[spec_t, spec_t, spec_t, spec_t,
                  pl.BlockSpec((1, N), lambda b, h, j: (h, 0)),
                  spec_s],
        out_specs=[spec_t, spec_s],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, N), r.dtype),
                   jax.ShapeDtypeStruct((B, H, N, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rwkv6_wkv",
    )(r, k, v, logw, u, s0)
    return y, sT
