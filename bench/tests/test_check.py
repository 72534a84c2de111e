"""The comparison's replay of a sampled draw: the noise the reference
draws from a request's seed, over its own top-p nucleus, picks the very
token the program's fused sampler picks from the same logits."""

import jax.numpy as jnp
import numpy as np
import pytest

from harness import check
from repro.core.sampling import base_key, sample_tokens


@pytest.mark.parametrize("temperature,top_p", [(0.8, 0.95), (1.3, 0.5)])
def test_replay_draws_what_the_program_draws(temperature, top_p):
    rng = np.random.default_rng(5)
    V, n, seed = 512, 24, 2 ** 31 - 77
    logits = (rng.standard_normal((n, V)) * 2.0).astype(np.float32)
    ctr = np.arange(n, dtype=np.int32)
    got = np.asarray(sample_tokens(
        jnp.asarray(logits), jnp.full((n,), temperature, jnp.float32),
        jnp.zeros((n,), jnp.int32), jnp.full((n,), top_p, jnp.float32),
        jnp.asarray(np.stack([base_key(seed)] * n)), jnp.asarray(ctr)))
    noise = check.replay_noise(seed, n, V)
    want = check.sampled_choice(logits, noise, temperature, top_p)
    assert (got == want).all()
    gap = check._sampled_gap(logits, noise, temperature, top_p, got)
    assert (gap == 0).all()


def test_sampled_gap_reads_a_wrong_draw():
    rng = np.random.default_rng(6)
    V, n, seed = 512, 64, 99
    logits = (rng.standard_normal((n, V)) * 2.0).astype(np.float32)
    noise = check.replay_noise(seed, n, V)
    draw = check.sampled_choice(logits, noise, 0.8, 0.95)
    # the next token over: another draw, at a gap of the row's spread
    other = check._sampled_gap(logits, noise, 0.8, 0.95, (draw + 1) % V)
    assert (other > 0).mean() > 0.9 and other.max() > 1.0
    # the least likely token lies outside the nucleus: the floor gap
    worst = logits.argmin(-1)
    far = check._sampled_gap(logits, noise, 0.8, 0.95, worst)
    floor = check._nucleus_floor(logits, 0.8, 0.95)
    rows = np.arange(n)
    assert (far >= (floor - logits[rows, worst]) / logits.std(-1)).all()
    assert far.min() > 1.0


def test_the_nucleus_edge_costs_only_its_height():
    # the reference's nucleus ends on a token that only just enters it,
    # and its noise wins the reference's draw; logits a hair higher
    # above it fill the nucleus without it, and the draw is another
    # token: that token reads the hair, not the edge token's noise
    rng = np.random.default_rng(7)
    V, k, t = 512, 200, 0.8
    logits = rng.standard_normal((1, V)) * 2.0
    order = np.argsort(-logits[0])
    p = np.exp(logits[0] / t - (logits[0] / t).max())
    p /= p.sum()
    top_p = p[order[:k]].sum() + 1e-7
    edge = order[k]
    noise = rng.gumbel(size=(1, V))
    noise[0, edge] = 50.0
    assert check.sampled_choice(logits, noise, t, top_p)[0] == edge
    higher = logits.copy()
    higher[0, order[:k]] += 1e-4
    other = check.sampled_choice(higher, noise, t, top_p)
    assert other[0] != edge
    assert check._sampled_gap(logits, noise, t, top_p, other)[0] == 0.0
    # the same token against a nucleus it plainly lies in reads its
    # whole deficit in score
    far = check._sampled_gap(logits, noise, t, 0.999, other)[0]
    assert far > 1.0
