"""The MLA + MoE counting functions pin the sizes of the cut
``deepseek-v3-7L-ep32`` worked out by hand in its configuration file and
in PERF.md, and agree with the parameters the program builds."""

import json

import jax
import pytest

from harness import counts, counts_mla_moe as cm
from harness.programs import DECODE, PREFILL
from harness.spec import Spec
from conftest import BENCH


@pytest.fixture(scope="module")
def cfg():
    return json.loads((BENCH / "configs" / "deepseek-v3-7L-ep32.json")
                      .read_text())


def test_sizes_at_the_cut(cfg):
    m = cfg["model"]
    # q down 7168x1536, q up 1536x128x192, kv down 7168x576,
    # kv up 512x128x256, out 128x128x7168
    assert cm.attention_params(m) == 187_105_280
    assert cm.dense_mlp_params(m) == 3 * 7168 * 18432
    assert cm.expert_params(m) == 3 * 7168 * 2048 == 44_040_192
    assert cm.param_count(m) == 5_945_090_048
    assert round(cm.param_count(m) / 1e9, 2) == 5.95
    assert cm.latent_bytes_per_token(m) == 8_064         # 7 x 576 x 2
    # 64 slots x 2048 positions of latent cache: 1.06 GB
    assert round(64 * 2048 * cm.latent_bytes_per_token(m) / 1e9, 2) == 1.06
    # 2,719 M matmul parameters a prompt token passes through: attention,
    # dense MLPs, shared experts and a quarter of one held expert a layer
    assert round(cm.active_linear_params(m) / 1e6) == 2719


def test_the_program_holds_what_is_counted(cfg):
    import run
    from repro.models import build_model
    from repro.configs import get_config
    params = jax.eval_shape(build_model(get_config(run.serve_arch(cfg)))
                            .init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(x.size for x in leaves) == cm.param_count(cfg["model"])


def test_decode_bytes_and_hit_share(cfg):
    m = cfg["model"]
    assert cm.expert_hit_share(m, 64) == pytest.approx(1 - (31 / 32) ** 64)
    assert cm.expert_hit_share(m, 64) == pytest.approx(0.869, abs=1e-3)
    # at 64 rows and no context: the non-expert matmuls (7.2 GB) and 87%
    # of the 2.82 GB of held experts
    no_ctx = cm.decode_step_bytes(m, 64, 0)
    assert 9.6e9 < no_ctx < 9.8e9
    assert (cm.decode_step_bytes(m, 64, 1000) - no_ctx
            == pytest.approx(1000 * 8064))


def test_readers_of_the_cell(cfg):
    """The cell's five per-layer readers on a hand-made reading: 100 decode
    steps of 20 ms at 64 rows and 64k live positions, 4,000 prompt tokens
    prefilled in 0.3 s, 6 s busy of 8."""
    spec = Spec(BENCH)
    names = [m["name"] for m in spec.metrics("dsv3.batch", "per_layer")]
    assert names == ["decode_step_ms.dsv3", "decode_roofline.dsv3",
                     "prefill_ms_per_ktok.dsv3", "prefill_mfu.dsv3",
                     "idle_share.dsv3"]
    m = cfg["model"]
    r = {"model": m, "peaks": counts.PEAKS["TPU v5 lite"], "window_s": 8.0,
         "trace": {"devices": 1, "busy_s": 6.0, "programs": {
             DECODE: {"count": 100, "ns": 100 * 20e6},
             PREFILL: {"count": 10, "ns": 0.3e9}}},
         "live": {"active": 64.0, "context": 64000.0},
         "counters": ({"prefill_tokens_total": 1000},
                      {"prefill_tokens_total": 5000})}
    got = {n: spec.reader(n)(r) for n in names}
    assert got["decode_step_ms.dsv3"] == pytest.approx(20.0)
    least = cm.decode_step_bytes(m, 64.0, 64000.0)
    assert got["decode_roofline.dsv3"] == pytest.approx(
        100 * least / 819e9 / 0.020)
    assert got["prefill_ms_per_ktok.dsv3"] == pytest.approx(75.0)
    assert got["prefill_mfu.dsv3"] == pytest.approx(
        100 * 2 * cm.active_linear_params(m) * 4000 / 0.3 / 197e12)
    assert got["idle_share.dsv3"] == pytest.approx(25.0)
    # nothing traced: every reader stays silent
    empty = dict(r, trace={"devices": 0, "busy_s": 0.0, "programs": {}},
                 live=None)
    assert all(spec.reader(n)(empty) is None for n in names)
