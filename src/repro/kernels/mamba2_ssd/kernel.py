"""Chunked Mamba-2 SSD scan — Pallas TPU kernel (zamba2 backbone hot spot).

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T ;  y_t = h_t C_t + (skip)

Grid: (batch, heads, num_chunks), chunk axis sequential; the (P, N) head
state lives in VMEM scratch across chunks.  The intra-chunk term uses the
SSD quadratic form with a log-space segment-sum decay matrix; decays are
scalar per (head, step) so the (c, c) decay matrix costs c^2 fp32 — tiny.

Per-chunk working set (c=128, P=64, N=64): x tile 32 KiB, B/C tiles
32 KiB each, (c,c) decay 64 KiB, state 16 KiB — VMEM-friendly, and the
three einsums ((c,c)x(c,P), (c,N)x(N,P), (c,P)x(c,N)) are MXU shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, h0_ref, y_ref, hT_ref, h_s,
            *, chunk: int, nc: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_s[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)              # (c, P)
    dt = dt_ref[0, 0].astype(jnp.float32)            # (c, 1)
    dA = da_ref[0, 0].astype(jnp.float32)            # (c, 1) log decay <= 0
    Bm = b_ref[0].astype(jnp.float32)                # (c, N)
    Cm = c_ref[0].astype(jnp.float32)                # (c, N)
    h = h_s[...]                                     # (P, N)

    c = x.shape[0]
    L = jnp.cumsum(dA[:, 0])                         # (c,) inclusive
    # segment-sum decay matrix: M[t,s] = exp(L_t - L_s), s <= t else 0
    diff = L[:, None] - L[None, :]
    tril = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    M = jnp.where(tril, jnp.exp(jnp.where(tril, diff, 0.0)), 0.0)
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (c,c)
    W = M * G                                        # (c,c)
    xdt = x * dt                                     # (c, P)
    y = jax.lax.dot_general(W, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (c,P)
    # inter-chunk state contribution: y_t += exp(L_t) C_t h^T
    Ch = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (c,P)
    y += jnp.exp(L)[:, None] * Ch
    y_ref[0, 0, :, :] = y.astype(y_ref.dtype)

    # state update: h' = exp(L_c) h + sum_s exp(L_c - L_s) dt_s x_s B_s^T
    Lc = L[-1]
    wdecay = jnp.exp(Lc - L)[:, None]                # (c,1)
    upd = jax.lax.dot_general(xdt * wdecay, Bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P,N)
    h_s[...] = jnp.exp(Lc) * h + upd

    @pl.when(j == nc - 1)
    def _finish():
        hT_ref[0, 0, :, :] = h_s[...].astype(hT_ref.dtype)


def ssd_bhtp(x, dt, dA, Bm, Cm, h0, *, chunk: int = 128,
             interpret: bool = True):
    """x (B,H,T,P); dt/dA (B,H,T,1); Bm/Cm (B,T,N); h0 (B,H,P,N).

    Returns (y (B,H,T,P), h_T (B,H,P,N)). T must divide by ``chunk``."""
    B, H, T, P = x.shape
    N = Bm.shape[-1]
    assert T % chunk == 0
    nc = T // chunk
    kern = functools.partial(_kernel, chunk=chunk, nc=nc)
    y, hT = pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, j: (b, j, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
                   jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mamba2_ssd",
    )(x, dt, dA, Bm, Cm, h0)
    return y, hT
