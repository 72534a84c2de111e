"""Blocked flash attention (prefill hot spot) — Pallas TPU kernel.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks), kv innermost and
sequential ("arbitrary") so the online-softmax running statistics can live
in VMEM scratch across kv steps.  GQA is handled in the k/v index_map
(q-head h reads kv-head h // group_size).

BlockSpec tiling: q/o tiles (q_blk, head_dim), k/v tiles (kv_blk, head_dim),
VMEM scratch m/l (q_blk, 1) and acc (q_blk, head_dim) in fp32.  With the
default q_blk = kv_blk = 128 and head_dim 64..128, the working set is
~(2*128*128 + 128*128)*4B ≈ 200 KiB — comfortably inside the ~16 MiB VMEM
per core, and all matmul dims are MXU-aligned (multiples of 128 where the
dtype requires it).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            scale: float, causal: bool, window: Optional[int],
            q_blk: int, kv_blk: int, nk: int):
    b = pl.program_id(0)          # batch row
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block
    seq_len = len_ref[b]          # this row's valid kv length (ragged)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)                    # (q_blk, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (kv_blk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = i * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 0)
    kpos = j * kv_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)
    mask = kpos < seq_len                                  # pad keys
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_old = m_s[...]                                       # (q_blk, 1)
    m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_old - m_new)
    l_s[...] = alpha * l_s[...] + p.sum(axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_s[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         window: Optional[int] = None,
                         seq_len: Optional[int] = None,
                         lengths=None,
                         q_blk: int = 128, kv_blk: int = 128,
                         interpret: bool = True):
    """q (B,H,Sq,hd); k/v (B,K,Skv,hd), H % K == 0. Sq/Skv already padded
    to block multiples; ``seq_len`` = number of valid kv positions, or
    ``lengths`` (B,) int32 for per-row ragged prefill."""
    B, H, Sq, hd = q.shape
    K, Skv = k.shape[1], k.shape[2]
    G = H // K
    assert Sq % q_blk == 0 and Skv % kv_blk == 0
    nq, nk = Sq // q_blk, Skv // kv_blk
    seq_len = Skv if seq_len is None else seq_len
    scale = 1.0 / (hd ** 0.5)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        q_blk=q_blk, kv_blk=kv_blk, nk=nk)

    if lengths is None:
        lengths = jnp.full((B,), seq_len, jnp.int32)
    # lengths is a scalar-prefetch operand, not a (1,) SMEM block: the TPU
    # lowering accepts a rank-1 block only when it spans the whole array
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, hd),
                         lambda b, h, i, j, len_ref: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_blk, hd),
                         lambda b, h, i, j, len_ref: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, kv_blk, hd),
                         lambda b, h, i, j, len_ref: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, hd),
                               lambda b, h, i, j, len_ref: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(lengths.astype(jnp.int32), q, k, v)
