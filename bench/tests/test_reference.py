"""The plain reference against the served path's own logits, at a tiny
size on the CPU: weights made from the seed must equal the program's
bit for bit, and prefill plus cached decode through the engine must give
the reference's logits (float32: to rounding; bfloat16: within the
rounding of bf16 activations).  The dense reference's own logits are
pinned bit for bit to those it gave before it moved to
``references/dense_gqa.py``."""

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from references.dense_gqa import Reference, fp8
from repro.configs import get_config
from repro.core import InferenceEngine
from repro.models.build import build_model

TINY = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
            head_dim=32, d_ff=256, vocab_size=512)


def tiny(arch, dtype, window):
    cfg = dataclasses.replace(get_config(arch), dtype=dtype,
                              sliding_window=window, **TINY)
    m = dict(TINY, dtype=dtype, sliding_window=window, rope_theta=1e4,
             norm_eps=1e-5)
    return cfg, m


@pytest.mark.parametrize("arch,dtype,window,tol", [
    ("yi-9b", "float32", None, 1e-4),
    ("h2o-danube-1.8b", "float32", 16, 1e-4),
    ("yi-9b", "bfloat16", None, 0.05),
])
def test_reference_matches_served_prefill_and_decode(arch, dtype, window,
                                                     tol):
    cfg, m = tiny(arch, dtype, window)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(11))
    ref = Reference(m, 11)
    assert bool(jnp.all(ref.head() == params["head"].astype(jnp.float32)))
    lw = ref.layer(1)
    assert bool(jnp.all(lw["w_down"] == params["layers"]["mlp"]["w_down"][1]
                        .astype(jnp.float32)))
    eng = InferenceEngine(model, params, max_len=64, max_batch=2)
    rng = np.random.default_rng(0)
    prompt, steps = 21, 6
    seq = rng.integers(1, 512, (1, prompt + steps)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        padded = np.zeros((1, 32), np.int32)
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
    got = np.stack(got)
    row = np.zeros((1, 64), np.int32)
    row[0, :prompt + steps] = seq[0]
    want = ref.logits([(row, np.asarray([prompt + steps], np.int32))],
                      [[list(range(prompt - 1, prompt + steps - 1))]])[0][0]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= tol * scale


def test_control_weights_are_coarser():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 256)) * 0.02
    q = fp8(w)
    rel = float(jnp.abs(q - w).max() / jnp.abs(w).max())
    assert 1e-3 < rel < 0.1


PIN = Path(__file__).resolve().parent / "data" / "dense_gqa_pin.npz"


@pytest.mark.parametrize("case,dtype,window,control,cols", [
    ("bf16_window", "bfloat16", 16, False, None),
    ("bf16_window_control", "bfloat16", 16, True, None),
    ("f32_cols", "float32", None, False, 64),
])
def test_dense_reference_logits_are_pinned(case, dtype, window, control,
                                           cols):
    # the pinned logits were written by the reference as it stood at
    # bench/harness/reference.py, on these tokens, seed and positions
    pin = np.load(PIN)
    m = dict(TINY, dtype=dtype, sliding_window=window, rope_theta=1e4,
             norm_eps=1e-5)
    blocks = [(pin["t1"], np.asarray([37], np.int32)),
              (pin["t2"], np.asarray([24, 17], np.int32))]
    pos = [[[5, 20, 36]], [[0, 11, 23], [3, 9, 16]]]
    got = Reference(m, 2 ** 31 - 99, control=control).logits(blocks, pos,
                                                              cols=cols)
    got = np.stack([r for blk in got for r in blk])
    assert got.dtype == np.float32 and got.shape == pin[case].shape
    assert np.array_equal(got, pin[case])
