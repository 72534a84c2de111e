"""The counting functions pin the sizes worked out by hand in PERF.md."""

import json

import pytest

from harness import counts
from conftest import BENCH


def model(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())[
        "model"]


def test_danube_sizes():
    m = model("h2o-danube-1.8b")
    # 24 x (attention 16,384,000 + SwiGLU 53,084,160) + 2 x 81,920,000
    assert counts.matmul_params(m) == 24 * (2560 * 2560 + 2 * 2560 * 640
                                            + 2560 * 2560
                                            + 3 * 2560 * 6912) + 2560 * 32000
    assert counts.param_count(m) == 1_831_075_840 + (2 * 24 + 1) * 2560
    assert round(counts.param_count(m) / 1e9, 2) == 1.83
    assert counts.kv_bytes_per_token(m) == 61_440      # 24 x 2 x 8 x 80 x 2
    # bf16 matrices, float32 norm scales
    assert counts.weight_bytes(m) == 2 * 1_831_075_840 + 4 * 49 * 2560


def test_yi_24_layer_sizes():
    # yi-9b (arXiv:2403.04652) at published widths, cut to 24 layers
    m = dict(model("h2o-danube-1.8b"), num_layers=24, d_model=4096,
             num_heads=32, num_kv_heads=4, head_dim=128, d_ff=11008,
             vocab_size=64000)
    assert counts.param_count(m) == 4_676_648_960 + (2 * 24 + 1) * 4096
    assert round(counts.param_count(m) / 1e9, 2) == 4.68
    assert counts.kv_bytes_per_token(m) == 49_152      # 24 x 2 x 4 x 128 x 2


def test_flops_and_bytes():
    m = model("h2o-danube-1.8b")
    n = counts.matmul_params(m)
    # one token attending over 1000 positions
    assert counts.token_flops(m, 1000) == 2 * n + 24 * 4 * 32 * 80 * 1000
    # a 3-token causal prompt: contexts 1, 2, 3
    assert counts.prompt_flops(m, 3) == (2 * n * 3
                                         + counts.attention_flops(m, 1) * 6)
    assert counts.decode_tick_bytes(m, [10, 20]) == (
        counts.weight_bytes(m) + 30 * 61_440)


def test_unknown_device_kind_is_an_error():
    assert counts.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks_for("cpu")
