"""DeepSeek-V3's mechanisms at a small size on the CPU: the held-expert
layer (one chip's share of an expert-parallel deployment), sigmoid
group-limited routing with its selection-only bias and 2.5 scale, YaRN
rotary frequencies and the mscale^2 softmax scale, dropless routing above
the capacity limit, and prefill plus cached decode against the full
forward pass."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MLAConfig, YarnConfig, get_config
from repro.core import InferenceEngine
from repro.models import attention as attn
from repro.models import build_model, moe
from repro.models.layers import apply_mlp, yarn_freqs

DSV3 = get_config("deepseek-v3-671b")


def small_moe_cfg(held=None, **kw):
    """16 experts in 4 groups, the best 2 groups kept, top-4 experts."""
    m = dataclasses.replace(DSV3.moe, num_experts=16, top_k=4, d_ff_expert=32,
                            n_group=4, topk_group=2, first_k_dense=1,
                            d_ff_dense=64, experts_held=held, **kw)
    return dataclasses.replace(
        DSV3, num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        head_dim=16, d_ff=32, vocab_size=256, dtype="float32", mtp=False,
        mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16, rope_head_dim=8,
                      nope_head_dim=16, v_head_dim=16), moe=m)


def numpy_route(x, router, bias, cfg):
    """Routing written out from the published equations, in numpy."""
    m = cfg.moe
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ router)))
    b = s + bias
    T, E = s.shape
    size = E // m.n_group
    group = np.sort(b.reshape(T, m.n_group, size), -1)[..., -2:].sum(-1)
    w = np.zeros_like(s)
    for t in range(T):
        kept = np.argsort(-group[t])[:m.topk_group]
        allowed = np.concatenate([np.arange(g * size, (g + 1) * size)
                                  for g in kept])
        chosen = allowed[np.argsort(-b[t, allowed])[:m.top_k]]
        w[t, chosen] = s[t, chosen] / s[t, chosen].sum() * m.routed_scaling
    return w


def dense_weights(p, x, cfg):
    """(T, E) combine weights the program's ``route`` gives."""
    top_p, top_i, _ = moe.route(p, x, cfg.moe)
    T, E = x.shape[0], cfg.moe.num_experts
    w = np.zeros((T, E))
    np.put_along_axis(w, np.asarray(top_i), np.asarray(top_p), axis=1)
    return w


@pytest.fixture(scope="module")
def layer():
    cfg = small_moe_cfg()
    p = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, cfg.d_model))
    return cfg, p, x


def test_routing_matches_the_published_equations(layer):
    cfg, p, x = layer
    want = numpy_route(np.asarray(x), np.asarray(p["router"]),
                       np.asarray(p["router_bias"]), cfg)
    np.testing.assert_allclose(dense_weights(p, x, cfg), want, atol=1e-5)


def test_bias_moves_choices_not_weights(layer):
    cfg, p, x = layer
    w = dense_weights(p, x, cfg)
    w0 = dense_weights(dict(p, router_bias=jnp.zeros_like(p["router_bias"])),
                       x, cfg)
    assert ((w > 0) != (w0 > 0)).any()       # the bias changed some choices
    s = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    chosen = w > 0
    # weights are the UNBIASED scores of the chosen experts, normalised,
    # times routed_scaling
    norm = np.where(chosen, s, 0.0).sum(-1, keepdims=True)
    np.testing.assert_allclose(w[chosen], (2.5 * s / norm)[chosen],
                               rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)


def test_excluded_groups_are_never_chosen(layer):
    cfg, p, x = layer
    w = dense_weights(p, x, cfg)
    b = np.asarray(jax.nn.sigmoid(x @ p["router"]) + p["router_bias"])
    group = np.sort(b.reshape(len(b), 4, 4), -1)[..., -2:].sum(-1)
    kept = np.argsort(-group, -1)[:, :2]
    for t, e in zip(*np.nonzero(w)):
        assert e // 4 in kept[t]
    # and groups do exclude: some token's best biased expert lies in a
    # dropped group
    best = b.argmax(-1) // 4
    assert any(g not in kept[t] for t, g in enumerate(best))


def test_expert_shares_sum_to_the_whole_layer(layer):
    """Summed over every share of 4 held experts, with the shared expert
    counted once, the parts equal the uncut layer."""
    cfg, p, x = layer
    whole, _ = moe.moe_block(p, x, cfg)
    held_cfg = small_moe_cfg(held=4)
    shared = apply_mlp({k.replace("ws_", "w_"): v for k, v in p.items()
                        if k.startswith("ws_")}, x, cfg)
    parts = []
    for first in range(0, 16, 4):
        pp = {k: (v[first:first + 4] if k.startswith("we_") else v)
              for k, v in p.items()}
        y, _, rows = moe.moe_block_held(pp, x, held_cfg, first=first)
        parts.append(y)
        assert int(rows[1]) == 40 * 4
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5)
    # the held rows of the four shares add up to every routed row
    assert sum(int(moe.moe_block_held(
        {k: (v[f:f + 4] if k.startswith("we_") else v)
         for k, v in p.items()}, x, held_cfg, first=f)[2][0])
        for f in range(0, 16, 4)) == 40 * 4


def test_held_layer_is_dropless_above_capacity():
    """A 300-token prefill through the held layer equals each token run
    alone; the served path never reaches capacity_for's dropping branch."""
    cfg = small_moe_cfg(held=4)
    p = moe.init_moe(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (300, cfg.d_model))
    assert moe.capacity_for(300, 4, 16) < 300      # the dense path would drop
    y = moe.moe_block_held(p, x, cfg)[0]
    one = jax.vmap(lambda t: moe.moe_block_held(p, t[None], cfg)[0][0])(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(one), atol=1e-5)


def test_served_path_never_calls_capacity(monkeypatch):
    cfg = small_moe_cfg(held=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))

    def refuse(*a, **k):
        raise AssertionError("capacity_for called on a held-expert config")

    monkeypatch.setattr(moe, "capacity_for", refuse)
    eng = InferenceEngine(model, params, max_len=256, max_batch=1)
    tokens = jnp.asarray(np.arange(1, 201, dtype=np.int32)[None] % 256)
    padded = jnp.pad(tokens, ((0, 0), (0, 56)))
    _, state = eng.prefill({"tokens": padded,
                            "lengths": jnp.asarray([200], jnp.int32)},
                           eng.new_state(1))
    eng.decode(jnp.asarray([5], jnp.int32), state)


def test_yarn_frequencies_and_scale():
    ys = DSV3.rope_scaling
    got = yarn_freqs(64, 1e4, ys)
    i = np.arange(32)
    extra = 1e4 ** (-2 * i / 64)
    d = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))  # noqa: E731
    low, high = math.floor(d(32)), math.ceil(d(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
        extra[-1] / 40, rel=1e-6)
    # 192^-1/2 x (0.1 ln 40 + 1)^2
    assert attn.mla_softmax_scale(DSV3) == pytest.approx(0.13523, abs=1e-5)
    plain = dataclasses.replace(DSV3, rope_scaling=None)
    assert attn.mla_softmax_scale(plain) == pytest.approx(192 ** -0.5)


def numpy_mla(p, x, cfg):
    """One MLA block over x (S, D), unabsorbed, in float64 numpy, from the
    published equations: YaRN frequencies on the two halves of the rope
    dims and the softmax scale 192^-1/2-style times mscale(40)^2."""
    m, ys = cfg.mla, cfg.rope_scaling
    P = {k: np.asarray(v, np.float64) for k, v in p.items()}
    S, H = x.shape[0], cfg.num_heads
    x = np.asarray(x, np.float64)
    rms = lambda t: t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-6)  # noqa: E731
    r = m.rope_head_dim
    i = np.arange(r // 2)
    extra = cfg.rope_theta ** (-2 * i / r)
    d = lambda n: (r * math.log(ys.original_max_position / (2 * math.pi * n))  # noqa: E731
                   / (2 * math.log(cfg.rope_theta)))
    lo, hi = math.floor(d(ys.beta_fast)), math.ceil(d(ys.beta_slow))
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    freqs = extra / ys.factor * ramp + extra * (1 - ramp)
    ang = np.arange(S)[:, None] * freqs

    def rope(t):                                       # (S, ..., r)
        c = np.cos(ang).reshape(S, *([1] * (t.ndim - 2)), r // 2)
        sn = np.sin(ang).reshape(c.shape)
        a, b = t[..., :r // 2], t[..., r // 2:]
        return np.concatenate([a * c - b * sn, a * sn + b * c], -1)

    q = (rms(x @ P["q_a"]) @ P["q_b"]).reshape(S, H, -1)
    qn, qr = q[..., :m.nope_head_dim], rope(q[..., m.nope_head_dim:])
    kv = x @ P["kv_a"]
    c, kr = rms(kv[:, :m.kv_lora_rank]), rope(kv[:, m.kv_lora_rank:])
    kvb = (c @ P["kv_b"]).reshape(S, H, -1)
    kn, v = kvb[..., :m.nope_head_dim], kvb[..., m.nope_head_dim:]
    scale = ((m.nope_head_dim + r) ** -0.5
             * (0.1 * ys.mscale_all_dim * math.log(ys.factor) + 1) ** 2)
    sc = (np.einsum("shd,thd->hst", qn, kn)
          + np.einsum("shr,tr->hst", qr, kr)) * scale
    sc = np.where(np.tril(np.ones((S, S), bool)), sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    out = np.einsum("hst,thd->shd", pr, v).reshape(S, -1)
    return out @ P["wo"]


def test_mla_prefill_matches_the_published_equations():
    """A prompt over one query block (``MLA_Q_BLOCK``) through the program's
    MLA, with YaRN and mscale^2, against the numpy equations."""
    cfg = small_moe_cfg()
    p = attn.init_mla(jax.random.PRNGKey(6), cfg)
    S = 2 * attn.MLA_Q_BLOCK
    x = jax.random.normal(jax.random.PRNGKey(7), (1, S, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(attn.mla_attention_block(p, x, cfg))[0]
    want = numpy_mla(p, x[0], cfg)
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_published_routing_and_rope_settings():
    m = DSV3.moe
    assert (m.scoring, m.n_group, m.topk_group, m.routed_scaling,
            m.num_experts, m.top_k) == ("sigmoid", 8, 4, 2.5, 256, 8)
    assert DSV3.rope_scaling == YarnConfig(
        factor=40.0, original_max_position=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    assert (DSV3.max_position, DSV3.norm_eps) == (163840, 1e-6)


@pytest.mark.parametrize("held,prompt", [(4, 140), (None, 60)])
def test_prefill_then_decode_matches_forward(held, prompt):
    """Prefill a prompt, decode through the latent cache in the absorbed
    form, and read the full forward's logits: with 4 of 16 experts held
    over a prompt above the capacity limit (dropless), and with every
    expert held below it (the uncut layer drops above 128 tokens)."""
    cfg = small_moe_cfg(held=held)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    rng = np.random.default_rng(0)
    steps = 5
    seq = rng.integers(1, 256, (1, prompt + steps)).astype(np.int32)
    eng = InferenceEngine(model, params, max_len=256, max_batch=1)
    with jax.default_matmul_precision("highest"):
        full = np.asarray(model.forward(params,
                                        {"tokens": jnp.asarray(seq)}))[0]
        padded = np.zeros((1, 256), np.int32)
        padded[0, :prompt] = seq[0, :prompt]
        logits, state = eng.prefill(
            {"tokens": jnp.asarray(padded),
             "lengths": jnp.asarray([prompt], jnp.int32)}, eng.new_state(1))
        got = [np.asarray(logits[0])]
        for j in range(steps - 1):
            logits, state = eng.decode(jnp.asarray(seq[:, prompt + j]),
                                       state)
            got.append(np.asarray(logits[0]))
    want = full[prompt - 1:prompt + steps - 1]
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4 * np.abs(
        want).max())
    rows = np.asarray(state["moe_rows"])
    assert rows[1] == 1 * 2 * 4          # one row, 2 expert layers, top-4
