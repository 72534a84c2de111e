"""The MLA + MoE reference against the served path's own logits, at a tiny
size on the CPU: weights made from the seed must equal the program's bit
for bit, and prefill plus cached (absorbed) decode through the engine,
over prompts longer than the MoE capacity's dropless limit, must give the
reference's (unabsorbed) logits.  The control (float8) must land farther
from them than the program does."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from references.mla_moe import Reference, route
from repro.configs import MLAConfig, YarnConfig, get_config
from repro.core import InferenceEngine
from repro.models.build import build_model

MLA = dict(q_lora_rank=64, kv_lora_rank=32, rope_head_dim=16,
           nope_head_dim=32, v_head_dim=32)
MOE = dict(num_experts=16, top_k=4, d_ff_expert=64, num_shared_experts=1,
           first_k_dense=1, d_ff_dense=128, norm_topk_prob=True,
           scoring="sigmoid", n_group=4, topk_group=2, routed_scaling=2.5,
           experts_held=4)
YARN = dict(factor=40.0, original_max_position=4096, beta_fast=32.0,
            beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)


def tiny(dtype):
    base = get_config("deepseek-v3-671b")
    cfg = dataclasses.replace(
        base, num_layers=3, d_model=128, num_heads=4, num_kv_heads=4,
        head_dim=32, d_ff=64, vocab_size=512, dtype=dtype, mtp=False,
        mla=MLAConfig(**MLA), rope_scaling=YarnConfig(**YARN),
        moe=dataclasses.replace(base.moe, **MOE))
    m = dict(num_layers=3, d_model=128, num_heads=4, vocab_size=512,
             dtype=dtype, rope_theta=1e4, norm_eps=1e-6, mla=MLA, moe=MOE,
             rope_scaling=YARN)
    return cfg, m


def served(cfg, params, seq, prompt, steps):
    """Logits of prefill over ``prompt`` tokens and ``steps - 1`` cached
    decode steps, at float32 matmul precision."""
    eng = InferenceEngine(model=build_model(cfg), params=params, max_len=256,
                          max_batch=2)
    with jax.default_matmul_precision("highest"):
        padded = np.zeros((1, 256), np.int32)
        padded[0, :prompt] = seq[0, :prompt]
        logits, state = eng.prefill(
            {"tokens": jnp.asarray(padded),
             "lengths": jnp.asarray([prompt], jnp.int32)},
            eng.new_state(1))
        got = [np.asarray(logits[0], np.float32)]
        for i in range(steps - 1):
            logits, state = eng.decode(jnp.asarray(seq[:, prompt + i]),
                                       state)
            got.append(np.asarray(logits[0], np.float32))
    return np.stack(got)


# float32: the program and the reference differ by the order of their
# float32 sums (absorbed against unabsorbed attention, one einsum against
# a loop over experts), so the logits agree to float32 rounding.
# bfloat16: the program rounds every activation to bf16 (8 bits of
# mantissa, 0.4% a rounding) and the reference keeps float32 activations
# over bf16 weights; through 3 layers that leaves a few percent of the
# logits' range, and a near-tie in routing may flip one expert.
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 0.06)])
def test_reference_matches_served_prefill_and_decode(dtype, tol):
    cfg, m = tiny(dtype)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    ref = Reference(m, 7)
    assert bool(jnp.all(ref.head() == params["head"].astype(jnp.float32)))
    lw = ref.layer(2)                             # the second expert layer
    pm = params["layers"]["moe"]
    for k in ("we_down", "router", "router_bias"):
        assert bool(jnp.all(lw[k] == pm[k][1].astype(jnp.float32))), k
    assert bool(jnp.all(lw["q_b"] == params["layers"]["attn"]["q_b"][1]
                        .astype(jnp.float32)))
    assert bool(jnp.all(ref.layer(0)["down"] == params["dense_layers"]
                        ["mlp"]["w_down"][0].astype(jnp.float32)))
    rng = np.random.default_rng(0)
    prompt, steps = 150, 6                       # above capacity's 128
    seq = rng.integers(1, 512, (1, prompt + steps)).astype(np.int32)
    got = served(cfg, params, seq, prompt, steps)
    row = np.zeros((1, 256), np.int32)
    row[0, :prompt + steps] = seq[0]
    want = ref.logits([(row, np.asarray([prompt + steps], np.int32))],
                      [[list(range(prompt - 1, prompt + steps - 1))]])[0][0]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= tol * scale


def test_control_is_coarser_than_the_program():
    cfg, m = tiny("bfloat16")
    params = build_model(cfg).init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(1)
    prompt, steps = 40, 4
    seq = rng.integers(1, 512, (1, prompt + steps)).astype(np.int32)
    got = served(cfg, params, seq, prompt, steps)
    row = np.zeros((1, 256), np.int32)
    row[0, :prompt + steps] = seq[0]
    blocks = [(row, np.asarray([prompt + steps], np.int32))]
    pos = [[list(range(prompt - 1, prompt + steps - 1))]]
    want = Reference(m, 3).logits(blocks, pos)[0][0]
    ctrl = Reference(m, 3, control=True).logits(blocks, pos)[0][0]
    err = np.abs(got - want).max()
    assert np.abs(ctrl - want).max() > 2 * err


def test_reference_routing_choices():
    """Bias moves the choice and not the weights; excluded groups are never
    chosen; weights are normalised unbiased scores times the scale."""
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.3, jnp.float32)
    w = np.asarray(route(logits, bias, 4, 4, 2, 2.5, True))
    w0 = np.asarray(route(logits, jnp.zeros(16), 4, 4, 2, 2.5, True))
    assert ((w > 0) != (w0 > 0)).any()               # choices moved
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = w > 0
    np.testing.assert_allclose(
        w[chosen], (2.5 * s / np.where(chosen, s, 0).sum(-1, keepdims=True)
                    )[chosen], rtol=1e-6)
    groups = np.nonzero(chosen)[1] // 4
    rows = np.nonzero(chosen)[0]
    b = (s + np.asarray(bias)).reshape(64, 4, 4)
    gscore = np.sort(b, -1)[..., -2:].sum(-1)
    kept = np.argsort(-gscore, -1)[:, :2]
    assert all(g in kept[r] for r, g in zip(rows, groups))
