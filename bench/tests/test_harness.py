"""CPU rehearsal of a whole run: a configuration and mixes dropped in
beside the shipped ones are found by name, with no edit to the harness,
and the same --seed gives the same schedule."""

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
