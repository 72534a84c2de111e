"""Mixture-of-Experts layer with capacity-based sort dispatch.

Top-k routing -> position-within-expert via sort -> scatter into per-expert
capacity buffers (E, C, D) -> dense per-expert matmuls -> gather back.
This is the GShard/Switch dropping formulation: compute is O(E*C*D*F)
(= actual expert FLOPs x capacity slack), NOT the O(T*E*C) one-hot-einsum
dispatch which would poison the roofline's compute term at 1M tokens.

Expert parallelism: the E dim of expert weights and buffers is sharded over
the mesh ``data`` axis (see repro.sharding); the token->buffer scatter and
buffer->token gather lower to the all-to-all pattern of real EP systems.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import compute_dtype, dense_init, init_mlp, apply_mlp
from repro.sharding import shard


# the selection-only routing bias (deepseek-v3's e_score_correction_bias)
# is drawn N(0, ROUTER_BIAS_STD^2) from the seed: random weights have no
# trained bias, and this scale moves some top-k choices (the sigmoid scores
# of a random router spread with a standard deviation near 0.2)
ROUTER_BIAS_STD = 0.05


def init_moe(key, cfg: ModelConfig):
    """Params for ONE MoE layer (stack with stack_init for the layer scan).
    The router spans every expert; the expert weights are those of the
    experts held here, [0, moe.held)."""
    m = cfg.moe
    d, fe = cfg.d_model, m.d_ff_expert
    n = m.held
    dt = compute_dtype(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.num_experts), jnp.float32),
        "we_gate": dense_init(ks[1], (n, d, fe), dt, in_axis=-2),
        "we_up": dense_init(ks[2], (n, d, fe), dt, in_axis=-2),
        "we_down": dense_init(ks[3], (n, fe, d), dt, in_axis=-2),
    }
    if m.scoring == "sigmoid":
        p["router_bias"] = ROUTER_BIAS_STD * jax.random.normal(
            jax.random.fold_in(ks[0], 1), (m.num_experts,), jnp.float32)
    if m.num_shared_experts:
        shared = init_mlp(ks[4], cfg, d_ff=fe * m.num_shared_experts)
        p.update({"ws_" + k.split("_", 1)[1]: v for k, v in shared.items()})
    return p


def route(p, x2, m: MoEConfig):
    """Top-k routing of tokens x2 (T, D) over every expert, in float32.

    softmax: probabilities over the experts, top-k of them.
    sigmoid (deepseek-v3, ``noaux_tc``): scores sigmoid(x W_r); the choice
    reads scores + ``router_bias``; each of ``n_group`` groups scores the
    sum of its two best biased scores, the ``topk_group`` best groups are
    kept and the top-k experts chosen among them; the weights are the
    unbiased scores of the chosen experts.
    Either way the weights are normalised to sum 1 (``norm_topk_prob``)
    and multiplied by ``routed_scaling``.

    Returns (weights (T,k), experts (T,k) int32, probs (T,E)): ``probs``
    sum to 1 per token, for the load-balance loss."""
    T = x2.shape[0]
    k, E, G = m.top_k, m.num_experts, m.n_group
    logits = x2.astype(jnp.float32) @ p["router"]          # (T,E)
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores + p["router_bias"]
        if G > 1:
            grouped = biased.reshape(T, G, E // G)
            best = jax.lax.top_k(grouped, min(2, E // G))[0].sum(-1)
            _, keep = jax.lax.top_k(best, m.topk_group)       # (T,topk_group)
            kept = jnp.zeros((T, G), bool).at[
                jnp.arange(T)[:, None], keep].set(True)
            biased = jnp.where(jnp.repeat(kept, E // G, axis=1), biased,
                               -jnp.inf)
        _, top_i = jax.lax.top_k(biased, k)
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        probs = scores / scores.sum(-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)             # (T,k)
    if m.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    if m.routed_scaling != 1.0:
        top_p = top_p * m.routed_scaling
    return top_p, top_i, probs


def _positions_in_expert(expert_ids: jnp.ndarray, num_experts: int):
    """pos[i] = rank of flat-assignment i within its expert group (sort-based)."""
    n = expert_ids.shape[0]
    sort_idx = jnp.argsort(expert_ids)                     # stable
    e_sorted = expert_ids[sort_idx]
    group_start = jnp.searchsorted(e_sorted, jnp.arange(num_experts),
                                   side="left")
    pos_sorted = jnp.arange(n) - group_start[e_sorted]
    return jnp.zeros((n,), jnp.int32).at[sort_idx].set(
        pos_sorted.astype(jnp.int32))


def capacity_for(num_tokens: int, top_k: int, num_experts: int,
                 capacity_factor: float = 1.25) -> int:
    """Per-expert slot count.  Capped at num_tokens: a token routes to an
    expert at most once, so C = T is DROPLESS — small batches (decode) get
    exact routing for free while big prefill/train batches stay capacity-
    bounded (GShard-style dropping)."""
    if num_tokens <= 128:
        return num_tokens          # dropless: decode batches route exactly
    c = math.ceil(num_tokens * top_k * capacity_factor / num_experts)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def moe_block(p, x, cfg: ModelConfig, *, capacity_factor: float = 1.25
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (..., D) -> (y: (..., D), aux_loss scalar).

    Flattens all leading dims into a token axis; static capacity per call.
    With the ``moe_ep`` optimization and an active mesh, dispatch runs the
    explicit expert-parallel all-to-all (moe_block_ep) instead of letting
    the SPMD partitioner replicate+all-reduce the dispatch buffers.
    """
    from repro import opt
    from repro.sharding import get_mesh
    mesh = get_mesh()
    m = cfg.moe
    if (opt.enabled("moe_ep") and mesh is not None):
        n_data = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
        n_model = mesh.shape.get("model", 1)
        tokens = 1
        for d_ in x.shape[:-1]:
            tokens *= d_
        if (m.num_experts % n_data == 0 and m.d_ff_expert % n_model == 0
                and tokens % n_data == 0):
            return moe_block_ep(p, x, cfg, capacity_factor=capacity_factor)
    m = cfg.moe
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    T = x2.shape[0]
    k, E = m.top_k, m.num_experts

    # --- routing (fp32) ----------------------------------------------------
    top_p, top_i, probs = route(p, x2, m)                  # (T,k)

    # --- positions & capacity ----------------------------------------------
    flat_e = top_i.reshape(T * k)
    pos = _positions_in_expert(flat_e, E)                  # (T*k,)
    C = capacity_for(T, k, E, capacity_factor)
    keep = pos < C
    slot = jnp.where(keep, pos, C)                         # dropped -> slot C

    # --- dispatch: scatter tokens into (E, C+1, D) buffers ------------------
    token_idx = jnp.repeat(jnp.arange(T), k)
    xw = x2[token_idx]                                     # (T*k, D)
    buf = jnp.zeros((E, C + 1, D), x2.dtype)
    buf = buf.at[flat_e, slot].add(xw)                     # unique (e,slot<C)
    buf = buf[:, :C]
    buf = shard(buf, "expert", None, None)

    # --- expert compute: dense per-expert matmuls ---------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["we_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["we_up"])
    h = shard(h, "expert", None, "ff")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["we_down"])
    out_buf = shard(out_buf, "expert", None, None)
    out_buf = jnp.concatenate(
        [out_buf, jnp.zeros((E, 1, D), out_buf.dtype)], axis=1)  # slot C = 0

    # --- combine: gather back, weight, sum over k ---------------------------
    y_flat = out_buf[flat_e, slot]                         # (T*k, D)
    y_flat = y_flat * (top_p.reshape(T * k, 1) * keep[:, None]).astype(
        y_flat.dtype)
    y = y_flat.reshape(T, k, D).sum(axis=1)

    # --- shared experts ------------------------------------------------------
    if m.num_shared_experts:
        sp = {"w_" + kk.split("_", 1)[1]: vv
              for kk, vv in p.items() if kk.startswith("ws_")}
        y = y + apply_mlp(sp, x2, cfg)

    # --- load-balance aux loss (Switch) --------------------------------------
    aux = _aux_loss(probs, top_i, E)

    return y.reshape(orig_shape).astype(x.dtype), aux


def _aux_loss(probs, top_i, E: int):
    """Switch load-balance loss: E * sum(mean prob x dispatch fraction)."""
    T, k = top_i.shape
    me = probs.mean(axis=0)                                 # (E,) mean prob
    one_hot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)   # (T,k,E)
    ce = one_hot.sum(axis=(0, 1)) / (T * k)                 # dispatch fraction
    return E * jnp.sum(me * ce)


# ---------------------------------------------------------------------------
# Held-expert layer: one chip's share of an expert-parallel deployment
# ---------------------------------------------------------------------------


def moe_block_held(p, x, cfg: ModelConfig, *, first: int = 0, valid=None):
    """The part of an MoE layer that the experts [first, first + held)
    give, plus the shared experts: what one chip of an expert-parallel
    deployment computes for its tokens, with no exchange.

    Tokens route over every expert (``route``); for each token, the
    chosen experts that are held here contribute w_e * down(silu(gate h)
    * up h).  Dropless at every token count: each held expert runs over
    every token (a buffer of T rows per held expert), with the weight of
    a token that did not choose it zero, so the memory is T x held x
    d_ff_expert and no capacity applies.

    ``valid`` (x's leading shape, bool) marks the real tokens for the
    counts.  Returns (y, aux, rows): rows (2,) int32 are the (token,
    expert) rows of valid tokens that fell to the held experts, and all
    that were routed."""
    m = cfg.moe
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    T = x2.shape[0]
    n = m.held
    with jax.named_scope("moe.route"):
        top_p, top_i, probs = route(p, x2, m)
        # (T, k, n): 1 where choice j of token t is held expert e
        mine = jax.nn.one_hot(top_i - first, n, dtype=jnp.float32)
        w = jnp.einsum("tkn,tk->nt", mine, top_p)           # (n, T)
        ok = (jnp.ones((T,), bool) if valid is None
              else valid.reshape(T))
        held_rows = jnp.sum(mine.sum((1, 2)) * ok).astype(jnp.int32)
        routed_rows = (jnp.sum(ok) * m.top_k).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        g = jnp.einsum("td,edf->etf", x2, p["we_gate"])
        u = jnp.einsum("td,edf->etf", x2, p["we_up"])
        h = jax.nn.silu(g) * u * w[:, :, None].astype(g.dtype)
        y = jnp.einsum("etf,efd->td", h, p["we_down"])
    if m.num_shared_experts:
        sp = {"w_" + kk.split("_", 1)[1]: vv
              for kk, vv in p.items() if kk.startswith("ws_")}
        y = y + apply_mlp(sp, x2, cfg)
    aux = _aux_loss(probs, top_i, m.num_experts)
    rows = jnp.stack([held_rows, routed_rows])
    return y.reshape(orig_shape).astype(x.dtype), aux, rows


def moe_layer(p, x, cfg: ModelConfig, *, valid=None):
    """The transformer's MoE layer: ``moe_block_held`` where the config
    holds a share of the experts (``experts_held < num_experts``), else
    ``moe_block`` over all of them.  Returns (y, aux, rows) as
    ``moe_block_held``; with every expert held, held rows = routed rows."""
    m = cfg.moe
    if m.held < m.num_experts:
        return moe_block_held(p, x, cfg, valid=valid)
    y, aux = moe_block(p, x, cfg)
    n_tok = (math.prod(x.shape[:-1]) if valid is None
             else jnp.sum(valid))
    routed = jnp.asarray(n_tok * m.top_k, jnp.int32)
    return y, aux, jnp.stack([routed, routed])


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (beyond-paper optimization `moe_ep`)
# ---------------------------------------------------------------------------
#
# The capacity-buffer formulation above leaves the token->expert scatter to
# the SPMD partitioner, which cannot partition an arbitrary scatter and
# falls back to replicate + all-reduce of the (E, C, D) buffers: ~13 TB a
# device a step for deepseek-v3 train_4k by the dry run's cost analysis (not
# a chip measurement; PERF.md holds those).
#
# moe_block_ep maps the communication pattern explicitly with shard_map:
#
#   1. each data shard routes its local tokens and packs per-expert send
#      buffers with a per-(source, expert) quota C_src;
#   2. ONE tiled all_to_all over the data axis delivers every expert's
#      tokens to the shard that owns it (experts are sharded over `data`);
#   3. expert FFN runs locally, with the ff dim sharded over `model`
#      (psum over `model` after the down-projection — standard TP);
#   4. the reverse all_to_all returns outputs to the token owners, which
#      combine top-k results locally.
#
# Collective traffic becomes the EP-minimal 2 x top_k x tokens x d_model
# per direction instead of all-reduced dispatch buffers.


def moe_block_ep(p, x, cfg: ModelConfig, *, capacity_factor: float = 1.25
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from jax.sharding import PartitionSpec as P
    from repro.sharding import get_mesh

    mesh = get_mesh()
    m = cfg.moe
    orig_shape = x.shape
    D = orig_shape[-1]
    x2 = x.reshape(-1, D)
    T = x2.shape[0]
    k, E = m.top_k, m.num_experts

    data_ax = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    model_ax = "model" if "model" in mesh.axis_names else None
    n_data = 1
    for a in data_ax:
        n_data *= mesh.shape[a]
    T_loc = T // n_data
    E_loc = E // n_data
    C_src = capacity_for(T_loc, k, E, capacity_factor)
    a2a_axis = data_ax if len(data_ax) > 1 else data_ax[0]

    dspec = data_ax if len(data_ax) > 1 else data_ax[0]
    x_spec = P(dspec, None)
    # the router's params (weights and, for sigmoid scoring, the bias) are
    # replicated: every shard routes its own tokens over every expert
    rparams = {kk: p[kk] for kk in ("router", "router_bias") if kk in p}
    router_spec = {kk: P(*(None,) * v.ndim) for kk, v in rparams.items()}
    wg_spec = P(dspec, None, model_ax)
    wd_spec = P(dspec, model_ax, None)

    has_shared = bool(m.num_shared_experts)
    shared_specs = (P(None, model_ax), P(None, model_ax), P(model_ax, None)) \
        if has_shared else ()
    shared_args = ((p["ws_gate"], p["ws_up"], p["ws_down"])
                   if has_shared else ())

    def body(x_loc, rp, wg, wu, wd, *shared):
        # ---- local routing -------------------------------------------------
        top_p, top_i, probs = route(rp, x_loc, m)
        flat_e = top_i.reshape(T_loc * k)
        pos = _positions_in_expert(flat_e, E)
        keep = pos < C_src
        slot = jnp.where(keep, pos, C_src)

        # ---- pack per-expert send buffers ----------------------------------
        token_idx = jnp.repeat(jnp.arange(T_loc), k)
        xw = x_loc[token_idx]
        send = jnp.zeros((E, C_src + 1, D), x_loc.dtype)
        send = send.at[flat_e, slot].add(xw)[:, :C_src]

        # ---- all-to-all: tokens travel to their expert's shard --------------
        recv = jax.lax.all_to_all(send, a2a_axis, split_axis=0,
                                  concat_axis=1, tiled=True)
        # recv: (E_loc, n_data * C_src, D)

        # ---- local expert FFN (ff sharded over `model`) ----------------------
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, wg)) \
            * jnp.einsum("ecd,edf->ecf", recv, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)
        if model_ax is not None:
            # reduce the ff-partial sums AND shard D in one collective: the
            # return all-to-all then carries D/n_model of the bytes, and the
            # full-D result is re-assembled ONCE per token at the very end
            # (psum here would move n_model x more bytes).
            out = jax.lax.psum_scatter(out, model_ax, scatter_dimension=2,
                                       tiled=True)   # (E_loc, C, D/m)

        # ---- reverse all-to-all + local combine ------------------------------
        Dl = out.shape[-1]
        back = jax.lax.all_to_all(out, a2a_axis, split_axis=1,
                                  concat_axis=0, tiled=True)  # (E,C_src,D/m)
        back = jnp.concatenate(
            [back, jnp.zeros((E, 1, Dl), back.dtype)], axis=1)
        y_flat = back[flat_e, slot]
        y_flat = y_flat * (top_p.reshape(T_loc * k, 1)
                           * keep[:, None]).astype(y_flat.dtype)
        y = y_flat.reshape(T_loc, k, Dl).sum(axis=1)   # (T_loc, D/m)

        # ---- shared experts (plain TP mlp) -----------------------------------
        if shared:
            wsg, wsu, wsd = shared
            hs = jax.nn.silu(x_loc @ wsg) * (x_loc @ wsu)
            ys = hs @ wsd
            if model_ax is not None:
                ys = jax.lax.psum_scatter(ys, model_ax,
                                          scatter_dimension=1, tiled=True)
            y = y + ys

        if model_ax is not None:
            y = jax.lax.all_gather(y, model_ax, axis=1,
                                   tiled=True)         # (T_loc, D)

        # ---- load-balance aux (global mean over data shards) ------------------
        me = probs.mean(axis=0)
        one_hot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)
        ce = one_hot.sum(axis=(0, 1)) / (T_loc * k)
        aux = E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, a2a_axis)
        if model_ax is not None:
            aux = jax.lax.pmean(aux, model_ax)
        return y, aux

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, router_spec, wg_spec, wg_spec, wd_spec)
        + shared_specs,
        out_specs=(x_spec, P()),
        check_vma=False)
    y, aux = fn(x2, rparams, p["we_gate"], p["we_up"], p["we_down"],
                *shared_args)
    return y.reshape(orig_shape).astype(x.dtype), aux
