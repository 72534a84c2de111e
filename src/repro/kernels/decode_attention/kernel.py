"""Flash-decode: one-token GQA attention against a long KV cache.

The decode_32k / long_500k hot spot.  All G query heads sharing a kv head
are processed together, so the inner matmul is (G, hd) x (hd, kv_blk) —
for GQA ratios 4..8 this keeps the MXU fed while each kv tile is streamed
through VMEM exactly once.

Grid: (batch, kv_heads, num_kv_blocks), kv innermost/sequential with the
online-softmax running stats in VMEM scratch.  Per-row valid ``lengths``
ride in as a scalar-prefetch operand (SMEM), giving the ragged masking
continuous batching needs; sliding-window serving masks kv below
(length - window).

VMEM working set with kv_blk=512, hd=128, G<=8:
2 * 512*128 (k,v tile) * 4B + G*128 acc ≈ 0.5 MiB.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, default_interpret


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            window: Optional[int], kv_blk: int, nk: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[b]                                   # this row's #valid
    q = q_ref[0, 0].astype(jnp.float32)                   # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (kv_blk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    hd = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / (hd ** 0.5))                           # (G, kv_blk)

    kpos = j * kv_blk + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    mask = kpos < length
    if window is not None:
        mask &= kpos > length - 1 - window
    s = jnp.where(mask, s, NEG_INF)

    m_old = m_s[...]
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


def decode_attention_bkgd(q, k, v, lengths, *, window: Optional[int] = None,
                          kv_blk: int = 512,
                          interpret: Optional[bool] = None):
    """q (B,K,G,hd); k/v (B,K,Smax,hd); lengths (B,) int32 -> (B,K,G,hd).

    ``interpret=None`` selects by backend: compiled on TPU, interpreter
    everywhere else (it used to hardcode True, silently interpreting on
    real TPUs); pass an explicit bool to override."""
    if interpret is None:
        interpret = default_interpret()
    B, K, G, hd = q.shape
    Smax = k.shape[2]
    assert Smax % kv_blk == 0
    nk = Smax // kv_blk
    kern = functools.partial(_kernel, window=window, kv_blk=kv_blk, nk=nk)
    # lengths is a scalar-prefetch operand, not a (1,) SMEM block: the TPU
    # lowering accepts a rank-1 block only when it spans the whole array
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, len_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, kv_blk, hd),
                         lambda b, h, j, len_ref: (b, h, j, 0)),
            pl.BlockSpec((1, 1, kv_blk, hd),
                         lambda b, h, j, len_ref: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, j, len_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(lengths, q, k, v)


# ---------------------------------------------------------------------------
# Paged variant: page-table indirection into a shared KV page pool
# ---------------------------------------------------------------------------


def _paged_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s,
                  acc_s, *, window: Optional[int], page_size: int, np_: int):
    """Same online-softmax loop as ``_kernel``, but the kv tile for grid
    step j is row b's j-th LOGICAL page, DMA'd from physical page
    ``pt_ref[b, j]`` of the pool (the BlockSpec index_map reads the
    scalar-prefetched page table).  ``lengths`` and the page table live in
    SMEM; the VMEM working set is one (page_size, hd) k/v tile — identical
    to the contiguous kernel with kv_blk=page_size.  Pages past a row's
    length alias the dump page and are masked off by ``kpos < length``."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)                   # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (page_size, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    hd = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / (hd ** 0.5))                           # (G, page_size)

    kpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < length
    if window is not None:
        mask &= kpos > length - 1 - window
    s = jnp.where(mask, s, NEG_INF)

    m_old = m_s[...]
    m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_old - m_new)
    l_s[...] = alpha * l_s[...] + p.sum(axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(j == np_ - 1)
    def _finish():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_s[...] / l).astype(o_ref.dtype)


def decode_attention_paged_bkgd(q, k_pages, v_pages, page_table, lengths, *,
                                window: Optional[int] = None,
                                interpret: Optional[bool] = None):
    """q (B,K,G,hd); k/v_pages (P,K,page_size,hd); page_table (B,MP) int32;
    lengths (B,) int32 -> (B,K,G,hd).

    Grid (B, K, MP) with the kv-page axis sequential; ``lengths`` and
    ``page_table`` ride in as scalar-prefetch operands so the k/v
    BlockSpec index_maps can turn logical page j into the physical pool
    page before the tile DMA issues."""
    if interpret is None:
        interpret = default_interpret()
    B, K, G, hd = q.shape
    page_size = k_pages.shape[2]
    MP = page_table.shape[1]
    kern = functools.partial(_paged_kernel, window=window,
                             page_size=page_size, np_=MP)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, MP),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd),
                         lambda b, h, j, len_ref, pt_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, j, len_ref, pt_ref:
                         (pt_ref[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, j, len_ref, pt_ref:
                         (pt_ref[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, hd), lambda b, h, j, len_ref, pt_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention_paged",
    )(lengths, page_table, q, k_pages, v_pages)
