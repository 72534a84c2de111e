"""CPU rehearsal of an MLA + MoE cell: a tiny DeepSeek-V3-style
configuration (YaRN MLA, sigmoid group-limited routing over 16 experts
with 4 held) dropped in beside the shipped ones is served through the
scheduler and compared with ``references/mla_moe.py`` by the harness,
with no edit to it.  The scheduler's expert-row counters move with the
traffic."""

import json

import pytest

import checkout

TINY_MLA_MOE = {
    "num_layers": 3, "d_model": 128, "num_heads": 4, "num_kv_heads": 4,
    "head_dim": 32, "d_ff": 64, "vocab_size": 512, "dtype": "bfloat16",
    "mtp": False,
    "mla": {"q_lora_rank": 64, "kv_lora_rank": 32, "rope_head_dim": 16,
            "nope_head_dim": 32, "v_head_dim": 32},
    "moe": {"num_experts": 16, "top_k": 4, "d_ff_expert": 64,
            "num_shared_experts": 1, "first_k_dense": 1, "d_ff_dense": 128,
            "n_group": 4, "topk_group": 2, "experts_held": 4},
}

CONFIG = {
    "name": "tiny-mla-moe", "source": "a tiny MLA + MoE model for CPU tests",
    "program_arch": "deepseek-v3-671b", "reference": "mla_moe",
    "overrides": TINY_MLA_MOE,
    "model": dict(TINY_MLA_MOE, rope_theta=10000.0, norm_eps=1e-06,
                  moe=dict(TINY_MLA_MOE["moe"], norm_topk_prob=True,
                           scoring="sigmoid", routed_scaling=2.5),
                  rope_scaling={"factor": 40.0,
                                "original_max_position": 4096,
                                "beta_fast": 32.0, "beta_slow": 1.0,
                                "mscale": 1.0, "mscale_all_dim": 1.0}),
    "members": 1,
    "serve": {"num_slots": 4, "max_len": 64, "max_batch": 2,
              "num_classes": 16, "max_queue": 256,
              "generate_token_budget": 100000},
    "reduced": [],
    # bf16 against the float32 reference at this size, greedy, four seeds:
    # the program read max 0.18-0.45 and mean 0.0031-0.0045, the float8
    # control max 0.96-1.36 and mean 0.112-0.131
    "limits": {"generate": {"max_gap_sd": 0.7, "mean_gap_sd": 0.02}}}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = checkout.make(tmp_path_factory.mktemp("checkout"))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tiny-mla-moe", "source": "test",
                           "file": "bench/configs/tiny-mla-moe.json",
                           "reduced": [], "why": "CPU rehearsal"})
    doc["workloads"].append({"name": "tiny.dsv3", "config": "tiny-mla-moe",
                             "traffic": "tiny_batch", "chips": 1,
                             "why": "CPU rehearsal"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "dsv3.batch" in m.get("workloads", []):
            m["workloads"].append("tiny.dsv3")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    (root / "bench" / "configs" / "tiny-mla-moe.json").write_text(
        json.dumps(CONFIG))
    return root


def test_mla_moe_cell_runs_by_name(tiny):
    out = checkout.drive(tiny, (
        "out = run.run('tiny.dsv3', 2**33 + 7, 4.0, False, "
        "require_tpu=False, bench_dir=run.Path('bench'), control=True)\n"
        "print(json.dumps(out))\n"))
    # --control judges the float8 control in the program's place
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["mean_gap_sd"]["value"] > 0.02
    assert set(out["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0


def test_mla_moe_cell_is_correct_and_counts_expert_rows(tiny):
    out = checkout.drive(tiny, (
        "rows = []\n"
        "close = run.Session.close\n"
        "def spy(self):\n"
        "    d = self.app.generation.entry_for().service.stats()['decode']\n"
        "    rows.append([d['moe_held_rows_total'],\n"
        "                 d['moe_routed_rows_total'],\n"
        "                 d['decode_tokens_total'],\n"
        "                 d['prefill_tokens_total']])\n"
        "    close(self)\n"
        "run.Session.close = spy\n"
        "out = run.run('tiny.dsv3', 424242, 4.0, False, "
        "require_tpu=False, bench_dir=run.Path('bench'))\n"
        "out['rows'] = rows[0]\n"
        "print(json.dumps(out))\n"))
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["gen_tokens_per_s"]["value"] > 0
    held, routed, decoded, prompt = out["rows"]
    # 2 expert layers x top-4 over every prompt token and every decode
    # row, and some of those rows fall to the 4 of 16 experts held
    assert routed >= 2 * 4 * prompt
    assert 0 < held < routed
