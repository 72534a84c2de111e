"""Sharding assignment for dry-run/launch inputs: params, optimizer state,
decode caches, and data batches.

Parameter specs come from repro.sharding's leaf-name rules (HSDP: d_model
dim -> data axis, head/ff/vocab dim -> model axis, expert dim -> data).

Decode-state specs are chosen per shape:
  * batch dim -> ("pod","data") when divisible (decode_32k, prefill_32k);
  * kv-head dim -> "model" when there are >= model_size kv heads;
  * otherwise the KV *sequence* dim -> "model";
  * long-context batch=1 -> sequence over ALL chips ("data","model").
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import data_axis_size, model_axis_size
from repro.sharding import param_specs
from repro.training.optimizer import OptState


def _batch_axes(mesh: Mesh):
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, str):
        return mesh.shape[entry]
    n = 1
    for a in entry:
        n *= mesh.shape[a]
    return n


def sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on any dim whose size isn't divisible by its mesh
    axes (jit in_shardings require exact divisibility — e.g. whisper's
    vocab 51865 can't split 16 ways)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        n = _axis_size(mesh, entry)
        out.append(entry if (n > 1 and dim % n == 0) or n == 1 else None)
    return P(*out)


def sanitize_tree(sds_tree, spec_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda sds, spec: sanitize_spec(spec, sds.shape, mesh),
        sds_tree, spec_tree)


def batch_spec(mesh: Mesh, batch: int, rank: int) -> P:
    axes = _batch_axes(mesh)
    n = data_axis_size(mesh)
    if batch % n == 0 and batch >= n:
        lead = axes if len(axes) > 1 else axes[0]
        return P(lead, *([None] * (rank - 1)))
    return P(*([None] * rank))


def batch_shardings(mesh: Mesh, batch_sds: dict) -> dict:
    out = {}
    for k, v in batch_sds.items():
        b = v.shape[0] if v.shape else 1
        out[k] = NamedSharding(mesh, batch_spec(mesh, b, len(v.shape)))
    return out


# --- decode / prefill state ---------------------------------------------------

_SEQ_CACHE_NAMES = {"k", "v", "xk", "xv", "shared_k", "shared_v"}
_LATENT_CACHE_NAMES = {"ckv", "krope"}


def _leaf_name(path) -> str:
    for part in reversed(path):
        key = getattr(part, "key", None)
        if isinstance(key, str):
            return key
    return ""


def state_specs(state_sds, cfg: ModelConfig, mesh: Mesh):
    dsize = data_axis_size(mesh)
    msize = model_axis_size(mesh)
    batch_lead = (("pod", "data") if "pod" in mesh.axis_names else "data")

    def one(path, leaf):
        name = _leaf_name(path)
        rank = len(leaf.shape)
        spec = [None] * rank
        if name == "length":
            return P(*spec)
        if name in _SEQ_CACHE_NAMES and rank >= 4:
            if rank == 4:   # stacked dense (L, B, S, K*hd): heads flat, minor
                b_ax, s_ax, k_ax = 1, 2, 3
                K = cfg.num_kv_heads
            else:           # (..., B, S, K, hd)
                b_ax, s_ax, k_ax = rank - 4, rank - 3, rank - 2
                K = leaf.shape[k_ax]
            B = leaf.shape[b_ax]
            if B % dsize == 0 and B >= dsize:
                spec[b_ax] = batch_lead
                if K % msize == 0 and K >= msize:
                    spec[k_ax] = "model"
                elif leaf.shape[s_ax] % msize == 0:
                    spec[s_ax] = "model"
            else:  # batch=1 long-context: shard seq over ALL chips
                if leaf.shape[s_ax] % (dsize * msize) == 0:
                    spec[s_ax] = (("pod", "data", "model")
                                  if "pod" in mesh.axis_names
                                  else ("data", "model"))
            return P(*spec)
        if name in _LATENT_CACHE_NAMES and rank >= 3:
            # (L, B, S, C)
            b_ax, s_ax = rank - 3, rank - 2
            B = leaf.shape[b_ax]
            if B % dsize == 0 and B >= dsize:
                spec[b_ax] = batch_lead
                if leaf.shape[s_ax] % msize == 0:
                    spec[s_ax] = "model"
            elif leaf.shape[s_ax] % (dsize * msize) == 0:
                spec[s_ax] = (("pod", "data", "model")
                              if "pod" in mesh.axis_names
                              else ("data", "model"))
            return P(*spec)
        if name == "wkv" and rank == 5:            # (L,B,H,N,N)
            if leaf.shape[1] % dsize == 0:
                spec[1] = batch_lead
            if leaf.shape[2] % msize == 0:
                spec[2] = "model"
            return P(*spec)
        if name in ("tm_shift", "cm_shift") and rank == 3:   # (L,B,D)
            if leaf.shape[1] % dsize == 0:
                spec[1] = batch_lead
            if leaf.shape[2] % msize == 0:
                spec[2] = "model"
            return P(*spec)
        if name == "conv" and rank == 4:           # (L,B,K-1,C)
            if leaf.shape[1] % dsize == 0:
                spec[1] = batch_lead
            if leaf.shape[3] % msize == 0:
                spec[3] = "model"
            return P(*spec)
        if name == "ssd" and rank == 5:            # (L,B,H,P,N)
            if leaf.shape[1] % dsize == 0:
                spec[1] = batch_lead
            if leaf.shape[2] % msize == 0:
                spec[2] = "model"
            return P(*spec)
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, state_sds)


def state_shardings(state_sds, cfg: ModelConfig, mesh: Mesh):
    specs = sanitize_tree(state_sds, state_specs(state_sds, cfg, mesh), mesh)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)


def param_shardings_for(params_sds, mesh: Mesh):
    specs = sanitize_tree(params_sds, param_specs(params_sds, mesh), mesh)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)


def opt_state_shardings(params_sds, mesh: Mesh) -> Any:
    pspec = param_shardings_for(params_sds, mesh)
    return OptState(
        step=NamedSharding(mesh, P()),
        mu=pspec,
        nu=pspec,
    )
