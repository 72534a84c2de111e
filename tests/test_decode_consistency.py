"""Decode-path correctness: prefill(prompt) + N x decode must reproduce the
full teacher-forced forward pass, for EVERY architecture family."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke_batch, smoke_model
from repro import opt
from repro.configs import get_config, reduce_for_smoke
from repro.models import build_model


@pytest.mark.parametrize("steps", [2])
def test_prefill_decode_matches_forward(arch, steps):
    cfg, model, params = smoke_model(arch)
    B, S = 2, 12
    batch = smoke_batch(cfg, B=B, S=S + steps, seed=3)
    tokens = batch["tokens"]
    full = model.forward(params, batch)

    extras = {k: v for k, v in batch.items()
              if k not in ("tokens", "labels")}
    state = model.init_state(B, S + steps + 4)
    pre_batch = dict(tokens=tokens[:, :S],
                     lengths=jnp.full((B,), S, jnp.int32), **extras)
    logits, state = model.prefill(params, pre_batch, state)

    scale = float(jnp.abs(full).max()) + 1.0
    tol = 2e-2 * scale if cfg.dtype == "bfloat16" else 1e-4 * scale
    assert float(jnp.abs(logits - full[:, S - 1]).max()) < tol
    for t in range(steps):
        logits, state = model.decode(params, tokens[:, S + t], state)
        assert float(jnp.abs(logits - full[:, S + t]).max()) < tol


def test_paged_prefill_decode_matches_dense():
    """The paged path must be BIT-identical to the dense one: prefill
    logits, then every decode step through the page table."""
    import numpy as np

    from repro.models import paged as P

    cfg, model, params = smoke_model("yi-9b")
    assert P.supports_paging(cfg)
    B, S, steps, ps = 2, 12, 2, 4
    batch = smoke_batch(cfg, B=B, S=S + steps, seed=3)
    tokens = batch["tokens"]
    full = model.forward(params, batch)

    MP = -(-(S + steps) // ps)
    table = np.asarray([[1 + b * MP + j for j in range(MP)]
                        for b in range(B)], np.int32)
    state = P.init_paged_state(cfg, B, B * MP + 1, ps, MP)
    nc = -(-S // ps)
    lengths = jnp.full((B,), S, jnp.int32)
    logits, state = P.paged_prefill(
        params, tokens[:, :S], lengths, state,
        jnp.zeros((B, 0), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.asarray(table[:, :nc]), cfg, page_size=ps)
    state["page_table"] = jnp.asarray(table)
    state["length"] = lengths

    dstate = model.init_state(B, S + steps)
    dlogits, dstate = model.prefill(
        params, dict(tokens=tokens[:, :S], lengths=lengths), dstate)
    assert np.array_equal(np.asarray(logits), np.asarray(dlogits))

    scale = float(jnp.abs(full).max()) + 1.0
    tol = 2e-2 * scale if cfg.dtype == "bfloat16" else 1e-4 * scale
    assert float(jnp.abs(logits - full[:, S - 1]).max()) < tol
    for t in range(steps):
        logits, state = P.paged_decode_step(
            params, tokens[:, S + t], state, cfg, page_size=ps)
        dlogits, dstate = model.decode(params, tokens[:, S + t], dstate)
        assert np.array_equal(np.asarray(logits), np.asarray(dlogits))
        assert float(jnp.abs(logits - full[:, S + t]).max()) < tol


def test_ragged_prefill_lengths(arch):
    """Rows with different prompt lengths decode independently."""
    cfg, model, params = smoke_model(arch)
    B, S = 2, 12
    batch = smoke_batch(cfg, B=B, S=S, seed=5)
    tokens = batch["tokens"]
    extras = {k: v for k, v in batch.items()
              if k not in ("tokens", "labels")}
    # row 0 has 8 valid tokens, row 1 has 12
    lengths = jnp.asarray([8, 12], jnp.int32)
    state = model.init_state(B, S + 4)
    logits, state = model.prefill(
        params, dict(tokens=tokens, lengths=lengths, **extras), state)
    # row 0 must match a clean batch-of-one prefill of its 8 tokens
    state1 = model.init_state(1, S + 4)
    tok1 = jnp.concatenate(
        [tokens[:1, :8], jnp.zeros((1, 4), jnp.int32)], axis=1)
    extras1 = {k: v[:1] for k, v in extras.items()}
    logits1, _ = model.prefill(
        params, dict(tokens=tok1, lengths=jnp.asarray([8], jnp.int32),
                     **extras1), state1)
    scale = float(jnp.abs(logits1).max()) + 1.0
    tol = 2e-2 * scale if cfg.dtype == "bfloat16" else 1e-3 * scale
    assert float(jnp.abs(logits[0] - logits1[0]).max()) < tol


def _tiny_dense():
    """h2o-danube-1.8b at smoke size (window 16) with 2 KV heads of
    head_dim 40: like danube's 80, not a multiple of the TPU's 128 lanes."""
    cfg = dataclasses.replace(
        reduce_for_smoke(get_config("h2o-danube-1.8b")),
        num_kv_heads=2, head_dim=40)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("ring", [True, False])
def test_decode_writes_one_position_per_row_in_place(ring):
    """One decode step through a donated state changes the stacked dense
    cache at [l, b, pos_b] alone (pos_b = length_b, modulo the ring's size
    in ring mode) and leaves every other position bitwise as it was."""
    cfg, model, params = _tiny_dense()
    B, S, max_len = 2, 21, 32
    lengths = np.asarray([13, 21], np.int32)
    rng = np.random.default_rng(11)
    tokens = rng.integers(1, cfg.vocab_size, (B, S)).astype(np.int32)
    tokens[0, lengths[0]:] = 0                       # right padding
    nxt = rng.integers(1, cfg.vocab_size, (B,)).astype(np.int32)
    with opt.flags(ring_cache=ring):
        state = model.init_state(B, max_len)
        Smax = state["cache"]["k"].shape[2]
        assert Smax == (cfg.sliding_window if ring else max_len)
        _, state = model.prefill(params, dict(tokens=jnp.asarray(tokens),
                                              lengths=jnp.asarray(lengths)),
                                 state)
        before = {k: np.asarray(v) for k, v in state["cache"].items()}
        decode = jax.jit(model.decode, donate_argnums=(2,))
        _, state = decode(params, jnp.asarray(nxt), state)
    assert np.array_equal(np.asarray(state["length"]), lengths + 1)
    written = np.zeros((cfg.num_layers, B, Smax), bool)
    written[:, np.arange(B), lengths % Smax] = True
    for name in ("k", "v"):
        after = np.asarray(state["cache"][name])
        assert after.shape == (cfg.num_layers, B, Smax,
                               cfg.num_kv_heads * cfg.head_dim)
        changed = np.any(after != before[name], axis=-1)
        assert np.array_equal(changed, written), name


@pytest.mark.parametrize("ring", [True, False])
def test_prefill_decode_matches_forward_across_ring_wrap(ring):
    """Prefill plus decode steps reproduce the windowed forward with the
    heads-flat cache; 13 + 6 positions wrap the 16-slot ring."""
    cfg, model, params = _tiny_dense()
    B, S, steps = 2, 13, 6
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S + steps), 0,
                                cfg.vocab_size)
    full = model.forward(params, dict(tokens=tokens))
    scale = float(jnp.abs(full).max()) + 1.0
    tol = 1e-4 * scale
    with opt.flags(ring_cache=ring):
        state = model.init_state(B, 32)
        logits, state = model.prefill(
            params, dict(tokens=tokens[:, :S],
                         lengths=jnp.full((B,), S, jnp.int32)), state)
        errs = [float(jnp.abs(logits - full[:, S - 1]).max())]
        decode = jax.jit(model.decode, donate_argnums=(2,))
        for t in range(steps):
            logits, state = decode(params, tokens[:, S + t], state)
            errs.append(float(jnp.abs(logits - full[:, S + t]).max()))
    assert max(errs) < tol, errs
