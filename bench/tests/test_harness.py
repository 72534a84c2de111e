"""CPU rehearsal of a whole run: a configuration, mixes and a reference
dropped in beside the shipped ones are found by name, with no edit to the
harness, and the same --seed gives the same schedule.  A configuration's
``model`` and ``overrides`` may nest."""

import dataclasses
import json

import pytest

import checkout
from harness import traffic


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return checkout.make(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.chat", {"itl_p50_ms", "setup_s"}),
    ("tiny.infer", {"infer_p95_ms", "setup_s"}),
    ("tiny.batch", {"gen_tokens_per_s", "setup_s"}),
])
def test_new_cell_runs_by_name(tiny, cell, metrics):
    out = checkout.drive(tiny, (
        f"out = run.run({cell!r}, 2**33 + 5, 4.0, False, "
        "require_tpu=False, bench_dir=run.Path('bench'))\n"
        "print(json.dumps(out))\n"))
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == metrics
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell,counted", [
    ("tiny.chat", {"tick_host_ms.chat"}),
    ("tiny.infer", {"infer_rows_per_forward"}),
    ("tiny.batch", {"slot_occupancy.batch"}),
])
def test_traced_run_reports_per_layer_metrics(tiny, cell, counted):
    # the CPU has no device plane: readers of the device trace find
    # nothing and their metrics are left out; counter readers still read
    out = checkout.drive(tiny, (
        "from harness import counts\n"
        "counts.PEAKS['cpu'] = counts.PEAKS['TPU v5 lite']\n"
        f"out = run.run({cell!r}, 31337, 4.0, True, "
        "require_tpu=False, bench_dir=run.Path('bench'))\n"
        "print(json.dumps(out))\n"))
    assert out["correct"] is True
    assert set(out["metrics"]) == counted
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["window_s"] > 0


def test_no_chip_no_result(tiny):
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tiny.chat",
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=tiny, env=dict(__import__("os").environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_same_seed_same_schedule(tiny):
    mix = traffic.load_mix(tiny / "bench" / "traffic", "tiny_chat")
    a = traffic.schedule(mix, 2**33 + 9, 20.0)
    b = traffic.schedule(mix, 2**33 + 9, 20.0)
    c = traffic.schedule(mix, 12345, 20.0)
    assert a == b
    # another seed: the same requests at the same times, other sampling
    # seeds and prompt tokens
    strip = [{k: v for k, v in r.items() if k != "seed"} for r in a]
    assert strip == [{k: v for k, v in r.items() if k != "seed"} for r in c]
    assert [r["seed"] for r in a] != [r["seed"] for r in c]
    assert (traffic.prompt_tokens(7, 3, 10, 512)
            == traffic.prompt_tokens(7, 3, 10, 512)).all()
    assert (traffic.prompt_tokens(7, 3, 10, 512)
            != traffic.prompt_tokens(8, 3, 10, 512)).any()
    # a Poisson process over the window at the mix's rate
    due = [r["due_s"] for r in a]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 20.0
    assert 40 <= len(a) <= 120
    body = traffic.payload(mix, a[0], 2**33 + 9, 512)
    assert body["temperature"] == 0.8 and body["seed"] == a[0]["seed"]
    greedy = traffic.load_mix(tiny / "bench" / "traffic", "tiny_batch")
    assert "temperature" not in traffic.payload(
        greedy, traffic.schedule(greedy, 1, 20.0)[0], 1, 512)


def test_cell_compares_with_the_reference_its_config_names(tiny):
    out = checkout.drive(tiny, (
        "names = []\n"
        "orig = run.Spec.reference\n"
        "def reference(self, name):\n"
        "    names.append(name)\n"
        "    return orig(self, name)\n"
        "run.Spec.reference = reference\n"
        "out = run.run('tiny.chat', 2**32 + 17, 4.0, False, "
        "require_tpu=False, bench_dir=run.Path('bench'))\n"
        "out['names'] = names\n"
        "print(json.dumps(out))\n"))
    assert out["names"] == ["dense_gqa"]
    assert out["correct"] is True, out["checks"]


# a reference that only a test's checkout holds: the dense one with its
# logits rolled by one along the vocabulary
ROLLED_REFERENCE = """
import numpy as np
from references.dense_gqa import Reference as Dense


class Reference(Dense):
    def logits(self, blocks, positions, cols=None):
        return [[np.roll(r, 1, axis=-1) for r in blk]
                for blk in super().logits(blocks, positions, cols)]
"""


def test_added_reference_is_reached_by_name(tmp_path):
    root = checkout.make(tmp_path)
    (root / "bench" / "references" / "rolled_gqa.py").write_text(
        ROLLED_REFERENCE)
    cfg = dict(checkout.TINY_CONFIG, name="tiny-rolled",
               reference="rolled_gqa")
    (root / "bench" / "configs" / "tiny-rolled.json").write_text(
        json.dumps(cfg))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tiny-rolled", "source": "test",
                           "file": "bench/configs/tiny-rolled.json",
                           "reduced": [], "why": "CPU rehearsal"})
    doc["workloads"].append({"name": "tiny.rolled", "config": "tiny-rolled",
                             "traffic": "tiny_chat", "chips": 1,
                             "why": "CPU rehearsal"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    out = checkout.drive(root, (
        "out = run.run('tiny.rolled', 2**33 + 3, 4.0, False, "
        "require_tpu=False, bench_dir=run.Path('bench'))\n"
        "print(json.dumps(out))\n"))
    assert out["correct"] is False
    c = out["checks"]["max_gap_sd"]
    assert c["value"] > c["limit"]


def test_config_without_reference_is_refused(tmp_path):
    from harness.spec import Spec
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text("{}")
    cfg = {k: v for k, v in checkout.TINY_CONFIG.items()
           if k != "reference"}
    (tmp_path / "bench" / "configs" / "tiny-gqa.json").write_text(
        json.dumps(cfg))
    with pytest.raises(KeyError, match="tiny-gqa.json.*reference"):
        Spec(tmp_path / "bench").config("tiny-gqa")


def test_serve_arch_nests_model_and_overrides():
    import run
    from repro.configs import get_config
    prog = get_config("qwen3-moe-235b-a22b")
    moe = dataclasses.asdict(prog.moe)
    # the program's own arch: a nested dict compared field by field
    own = {"name": "qwen3-moe-235b-a22b",
           "program_arch": "qwen3-moe-235b-a22b",
           "model": {"num_layers": 94, "moe": moe}}
    assert run.serve_arch(own) == "qwen3-moe-235b-a22b"
    # a nested override replaces the fields it gives and keeps the rest;
    # the file's model names only some of the sub-config's fields
    cut = {"name": "qwen3-moe-16e", "program_arch": "qwen3-moe-235b-a22b",
           "overrides": {"num_layers": 2, "moe": {"num_experts": 16}},
           "model": {"num_layers": 2, "d_model": 4096,
                     "moe": {"num_experts": 16, "top_k": 8}}}
    assert run.serve_arch(cut) == "qwen3-moe-16e"
    got = get_config("qwen3-moe-16e")
    assert got.moe == dataclasses.replace(prog.moe, num_experts=16)
    assert got.num_layers == 2 and prog.num_layers == 94
    assert prog.moe.num_experts == 128
    wrong = dict(cut, model={"num_layers": 2,
                             "moe": dict(moe, num_experts=16, top_k=4)})
    with pytest.raises(ValueError, match=r"moe\.top_k is 4 in the file "
                                         r"but 8 in the program"):
        run.serve_arch(wrong)
    with pytest.raises(ValueError, match=r"moe\.num_experts is 64 .* 128"):
        run.serve_arch(dict(own, model={"moe": {"num_experts": 64}}))
    with pytest.raises(ValueError, match=r"moe\.num_experts_x is 1"):
        run.serve_arch(dict(own, model={"moe": {"num_experts_x": 1}}))
