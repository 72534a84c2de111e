"""Compile the served path's Pallas kernels and the full-width decode step
for a described TPU v5e chip.

Nothing runs: XLA's TPU compiler builds each program for a chip that is
described, not attached, so a kernel the chip's lowering refuses (say, an
SMEM block the tiling rules reject) or a step that does not fit 16 GiB of
HBM fails here, in the CPU test suite, instead of on the chip.  The
topology is described inside a fixture: only the test worker that runs
this file loads the TPU compiler library.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.engine import InferenceEngine
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.flash_attention import flash_attention
from repro.models import build_model

HBM_BYTES = 16 * 2 ** 30            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU program written to a persistent cache cannot be read back
    # without the chip: keep these compiles out of any cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "yi-9b"])
def test_paged_decode_kernel_compiles(one_chip, arch):
    cfg = get_config(arch)
    B, P, ps, MP = 8, 129, 16, 16
    K, hd = cfg.num_kv_heads, cfg.head_dim
    text = _compiled_text(
        lambda q, k, v, pt, ln: decode_ops.paged_decode_attention(
            q, k, v, pt, ln, interpret=False),
        _sds((B, cfg.num_heads, hd), jnp.bfloat16, one_chip),
        _sds((P, ps, K, hd), jnp.bfloat16, one_chip),
        _sds((P, ps, K, hd), jnp.bfloat16, one_chip),
        _sds((B, MP), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_dense_decode_kernel_compiles(one_chip):
    cfg = get_config("h2o-danube-1.8b")
    B, Smax = 8, 1024
    K, hd = cfg.num_kv_heads, cfg.head_dim
    text = _compiled_text(
        lambda q, k, v, ln: decode_ops.decode_attention(
            q, k, v, ln, interpret=False),
        _sds((B, cfg.num_heads, hd), jnp.bfloat16, one_chip),
        _sds((B, Smax, K, hd), jnp.bfloat16, one_chip),
        _sds((B, Smax, K, hd), jnp.bfloat16, one_chip),
        _sds((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_flash_attention_kernel_compiles_with_lengths(one_chip):
    cfg = get_config("h2o-danube-1.8b")
    B, S = 4, 256
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    text = _compiled_text(
        lambda q, k, v, ln: flash_attention(q, k, v, lengths=ln,
                                            interpret=False),
        _sds((B, S, H, hd), jnp.bfloat16, one_chip),
        _sds((B, S, K, hd), jnp.bfloat16, one_chip),
        _sds((B, S, K, hd), jnp.bfloat16, one_chip),
        _sds((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def _compile_decode_step(one_chip, cfg, B, max_len):
    """Compile the fused decode+sample step the scheduler runs every tick
    (``InferenceEngine._decode_sample``) for the described chip."""
    model = build_model(cfg)
    place = lambda t: jax.tree_util.tree_map(           # noqa: E731
        lambda x: _sds(x.shape, x.dtype, one_chip), t)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    engine = InferenceEngine(model, params, max_len=max_len, max_batch=B)
    state = place(model.state_specs(B, max_len))
    compiled = engine._decode_sample.lower(
        params, _sds((B,), jnp.int32, one_chip), state,
        _sds((B,), jnp.float32, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.float32, one_chip),
        _sds((B, 2), jnp.uint32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    return compiled, state


def test_full_width_decode_step_fits_one_chip(one_chip):
    """The fused decode+sample step the scheduler runs every tick, at
    h2o-danube-1.8b's published widths (bf16, 24 layers), fits one chip."""
    compiled, _ = _compile_decode_step(one_chip, get_config("h2o-danube-1.8b"),
                                       8, 256)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 3 * 2 ** 30     # the bf16 weights
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"


_COPY = re.compile(r"= \w+\[([\d,]*)\]\{([^}]*)\} copy\(")


@pytest.mark.parametrize("arch,num_layers,slots", [
    ("h2o-danube-1.8b", None, 16),      # head_dim 80: not a lane multiple
    ("yi-9b", 24, 24),                  # head_dim 128
])
def test_decode_step_updates_cache_in_place(one_chip, arch, num_layers,
                                            slots):
    """The served decode step writes each new token into the donated dense
    cache in place.  Carried through the layer scan, the stacked cache
    needs no second copy as a temporary (a scan output restacked per layer
    held one: 2.23 GB at danube's 16 x 2048, 2.42 GB at yi-9b's 24 x
    2048), and no copy in HBM moves the whole cache or one layer's slice
    of it.  Each layer's K and V slices are read from HBM once; the
    transposes the attention's dots ask of them stay in on-chip memory
    (memory space S(1))."""
    cfg = get_config(arch)
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    compiled, state = _compile_decode_step(one_chip, cfg, slots, 2048)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, mem.temp_size_in_bytes
    stacked = state["cache"]["k"].shape
    whole, layer = math.prod(stacked), math.prod(stacked[1:])
    assert stacked[:3] == (cfg.num_layers, slots, 2048)
    for m in _COPY.finditer(compiled.as_text()):
        n = math.prod(int(d) for d in m.group(1).split(",") if d)
        on_chip = "S(" in m.group(2)
        assert n != whole, m.group(0)
        assert on_chip or n != layer, m.group(0)


# deepseek-v3-671b at the benchmark's cut (bench/configs/
# deepseek-v3-7L-ep32.json): 7 layers, 8 of 256 experts held, no MTP,
# 64 decode slots of 2048 positions, prefill groups of up to 2 prompts
DSV3_SLOTS, DSV3_GROUP, DSV3_LEN = 64, 2, 2048


def _dsv3_cut():
    base = get_config("deepseek-v3-671b")
    return dataclasses.replace(
        base, num_layers=7, mtp=False,
        moe=dataclasses.replace(base.moe, experts_held=8))


def test_deepseek_v3_cut_fits_and_updates_latent_cache_in_place(one_chip):
    """The served decode step of the cut writes each token into the
    donated latent cache (``ckv``, ``krope``) in place, with no copy of a
    whole stacked leaf or of a layer's slice in HBM; and the weights, the
    pooled cache and a 2 x 2048-token prefill's temporaries fit one
    chip's HBM together."""
    cfg = _dsv3_cut()
    compiled, state = _compile_decode_step(one_chip, cfg, DSV3_SLOTS,
                                           DSV3_LEN)
    dec = compiled.memory_analysis()
    text = compiled.as_text()
    for stack in ("cache", "cache_dense"):
        for leaf in ("ckv", "krope"):
            shape = state[stack][leaf].shape
            assert shape[1:3] == (DSV3_SLOTS, DSV3_LEN)
            whole, layer = math.prod(shape), math.prod(shape[1:])
            for m in _COPY.finditer(text):
                n = math.prod(int(d) for d in m.group(1).split(",") if d)
                assert n != whole, m.group(0)
                assert "S(" in m.group(2) or n != layer, m.group(0)
    model = build_model(cfg)
    place = lambda t: jax.tree_util.tree_map(           # noqa: E731
        lambda x: _sds(x.shape, x.dtype, one_chip), t)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    engine = InferenceEngine(model, params, max_len=DSV3_LEN,
                             max_batch=DSV3_GROUP)
    pre = engine._prefill.lower(
        params, {"tokens": _sds((DSV3_GROUP, DSV3_LEN), jnp.int32, one_chip),
                 "lengths": _sds((DSV3_GROUP,), jnp.int32, one_chip)},
        place(model.state_specs(DSV3_GROUP, DSV3_LEN))).compile()
    pm = pre.memory_analysis()
    assert dec.argument_size_in_bytes > 12 * 10 ** 9    # weights + cache
    # resident weights and pooled cache, the prefill's temporaries and its
    # group cache, as the scheduler holds them while it admits
    peak = (dec.argument_size_in_bytes + pm.temp_size_in_bytes
            + pm.output_size_in_bytes)
    assert peak < HBM_BYTES, f"{peak / 2 ** 30:.2f} GiB"
    assert dec.temp_size_in_bytes < 2 ** 30, dec.temp_size_in_bytes
