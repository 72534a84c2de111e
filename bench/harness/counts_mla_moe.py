"""Yardsticks of a DeepSeek-V3-style configuration (MLA attention, leading
dense layers, then expert layers of which this chip holds a share):
parameters, bytes and operations counted from the file's ``model``.
Kept beside ``counts.py``, which stays dense-only, so that no later
change to the program can move them.

Sizes at the cut ``deepseek-v3-7L-ep32`` (pinned by
``bench/tests/test_counts_mla_moe.py``): 187.11 M parameters of attention
a layer, 396.4 M in a dense layer's MLP, 44.04 M in an expert, a 1.84 M
router; 5,945,090,048 parameters in all; 8,064 B of latent cache a
token."""

from __future__ import annotations

from typing import Any, Dict

from harness.counts import DTYPE_BYTES


def _layers(m: Dict[str, Any]):
    n_dense = m["moe"]["first_k_dense"]
    return n_dense, m["num_layers"] - n_dense


def attention_params(m: Dict[str, Any]) -> int:
    """MLA projections of one layer: q down and up, kv down (latent and
    rope key), kv up (per-head keys and values), output."""
    a, d, H = m["mla"], m["d_model"], m["num_heads"]
    qk = a["nope_head_dim"] + a["rope_head_dim"]
    return (d * a["q_lora_rank"] + a["q_lora_rank"] * H * qk
            + d * (a["kv_lora_rank"] + a["rope_head_dim"])
            + a["kv_lora_rank"] * H * (a["nope_head_dim"] + a["v_head_dim"])
            + H * a["v_head_dim"] * d)


def dense_mlp_params(m: Dict[str, Any]) -> int:
    return 3 * m["d_model"] * m["moe"]["d_ff_dense"]


def expert_params(m: Dict[str, Any]) -> int:
    """One expert (or one shared expert): SwiGLU gate, up and down."""
    return 3 * m["d_model"] * m["moe"]["d_ff_expert"]


def held(m: Dict[str, Any]) -> int:
    e = m["moe"]
    return e["experts_held"] or e["num_experts"]


def param_count(m: Dict[str, Any]) -> int:
    """Every parameter the chip holds: embedding, head, attention, dense
    MLPs, held and shared experts, routers and their biases, and the
    RMSNorm scales (two a layer, the q and kv latents' a layer, a final
    one)."""
    n_dense, n_moe = _layers(m)
    e, a, d = m["moe"], m["mla"], m["d_model"]
    L = m["num_layers"]
    per_moe = ((held(m) + e["num_shared_experts"]) * expert_params(m)
               + d * e["num_experts"] + e["num_experts"])
    norms = (2 * L + 1) * d + L * (a["q_lora_rank"] + a["kv_lora_rank"])
    return (2 * m["vocab_size"] * d + L * attention_params(m)
            + n_dense * dense_mlp_params(m) + n_moe * per_moe + norms)


def latent_bytes_per_token(m: Dict[str, Any]) -> int:
    """Latent cache one token adds over all layers: its compressed kv and
    its rope key."""
    a = m["mla"]
    return (m["num_layers"] * (a["kv_lora_rank"] + a["rope_head_dim"])
            * DTYPE_BYTES[m["dtype"]])


def expert_hit_share(m: Dict[str, Any], batch: float) -> float:
    """Expected share of the held experts that ``batch`` tokens touch,
    each token choosing top_k of num_experts experts evenly:
    1 - (1 - top_k / num_experts)^batch."""
    e = m["moe"]
    return 1.0 - (1.0 - e["top_k"] / e["num_experts"]) ** batch


def decode_step_bytes(m: Dict[str, Any], batch: float,
                      context: float) -> float:
    """Least bytes one decode step of ``batch`` active rows must read:
    every weight but the embedding (of which it reads ``batch`` rows) and
    the held experts (each read only where a row routes to it, at the
    expected hit share), and the live latent cache of ``context``
    positions summed over the rows.  Routers are float32."""
    n_dense, n_moe = _layers(m)
    e, d = m["moe"], m["d_model"]
    w = DTYPE_BYTES[m["dtype"]]
    mats = (m["num_layers"] * attention_params(m)
            + n_dense * dense_mlp_params(m)
            + n_moe * e["num_shared_experts"] * expert_params(m)
            + d * m["vocab_size"] + batch * d)
    experts = n_moe * held(m) * expert_params(m) * expert_hit_share(m, batch)
    routers = n_moe * (d + 1) * e["num_experts"] * 4
    return ((mats + experts) * w + routers
            + latent_bytes_per_token(m) * context)


def active_linear_params(m: Dict[str, Any]) -> float:
    """Matmul parameters one prompt token passes through below the head:
    attention projections, dense MLPs, shared experts and, on average,
    top_k x held / num_experts held experts (the experts it routes to
    that this chip holds).  Routers and the quadratic attention term are
    left out, so FLOPs counted from it are a lower bound."""
    n_dense, n_moe = _layers(m)
    e = m["moe"]
    per_token_experts = (e["num_shared_experts"]
                         + e["top_k"] * held(m) / e["num_experts"])
    return (m["num_layers"] * attention_params(m)
            + n_dense * dense_mlp_params(m)
            + n_moe * per_token_experts * expert_params(m))
