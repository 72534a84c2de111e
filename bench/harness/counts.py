"""Yardsticks: the chip's published peaks, and operations and bytes
counted from a configuration's shapes.  Copied here from the program's
own arithmetic (``repro.analysis.roofline``) so that no later change to
the program can move them."""

from __future__ import annotations

from typing import Any, Dict, Iterable

# Keyed by jax's ``device.device_kind``; a kind missing here is an error.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
# of HBM at 819 GB/s per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
}

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def _dims(m: Dict[str, Any]):
    hd = m["head_dim"]
    return (m["num_layers"], m["d_model"], m["num_heads"],
            m["num_kv_heads"], hd, m["d_ff"], m["vocab_size"])


def matmul_params(m: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix product for every token: the
    attention and SwiGLU projections of each layer, and the output head
    (the embedding is a lookup)."""
    L, d, H, K, hd, ff, V = _dims(m)
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    mlp = 3 * d * ff
    return L * (attn + mlp) + d * V


def param_count(m: Dict[str, Any]) -> int:
    """Every parameter: projections, embedding, head, and RMSNorm scales
    (two per layer and a final one)."""
    L, d, *_ , V = _dims(m)
    return matmul_params(m) + V * d + (2 * L + 1) * d


def weight_bytes(m: Dict[str, Any]) -> int:
    """Bytes of one model's weights as served: matrices in the stated
    dtype, RMSNorm scales in float32."""
    L, d, *_ , V = _dims(m)
    mats = matmul_params(m) + V * d
    return mats * DTYPE_BYTES[m["dtype"]] + (2 * L + 1) * d * 4


def kv_bytes_per_token(m: Dict[str, Any]) -> int:
    """Key and value bytes one token adds to the cache over all layers."""
    L, d, H, K, hd, ff, V = _dims(m)
    return L * 2 * K * hd * DTYPE_BYTES[m["dtype"]]


def attention_flops(m: Dict[str, Any], context: int) -> int:
    """Score and value products of one query over ``context`` keys."""
    L, d, H, K, hd, ff, V = _dims(m)
    return L * 2 * 2 * H * hd * context


def token_flops(m: Dict[str, Any], context: int) -> int:
    """Model FLOPs of one token that attends over ``context`` positions
    (itself included): 2 per matmul parameter plus attention."""
    return 2 * matmul_params(m) + attention_flops(m, context)


def prompt_flops(m: Dict[str, Any], length: int, *,
                 last_only_head: bool = False) -> int:
    """Model FLOPs of a causal forward over ``length`` real tokens.
    ``last_only_head``: the head runs on the last position only."""
    L, d, H, K, hd, ff, V = _dims(m)
    body = 2 * (matmul_params(m) - d * V) * length
    head = 2 * d * V * (1 if last_only_head else length)
    attn = attention_flops(m, 1) * length * (length + 1) // 2
    return body + head + attn


def decode_tick_bytes(m: Dict[str, Any], contexts: Iterable[int]) -> int:
    """Least bytes one decode step must read: the weights once and the
    live keys and values of every active slot."""
    return weight_bytes(m) + kv_bytes_per_token(m) * sum(contexts)
