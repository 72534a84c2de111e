"""A throwaway checkout holding the benchmark, the program (linked) and a
tiny configuration and mixes dropped in beside the shipped ones, so that
the harness can be driven end to end on the CPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_MODEL = {"num_layers": 2, "d_model": 128, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 32, "d_ff": 256,
              "vocab_size": 512, "sliding_window": None,
              "rope_theta": 10000.0, "norm_eps": 1e-05,
              "tie_embeddings": False, "dtype": "bfloat16"}

TINY_CONFIG = {
    "name": "tiny-gqa", "source": "a tiny dense-GQA model for CPU tests",
    "program_arch": "yi-9b", "reference": "dense_gqa",
    "overrides": {k: TINY_MODEL[k] for k in
                  ("num_layers", "d_model", "num_heads", "num_kv_heads",
                   "head_dim", "d_ff", "vocab_size", "dtype")},
    "model": TINY_MODEL, "members": 2,
    "serve": {"num_slots": 4, "max_len": 64, "max_batch": 4,
              "num_classes": 16, "max_queue": 256,
              "generate_token_budget": 100000},
    "reduced": [],
    # readings at this size (test_faults.py): bf16 runs 0.004-0.033, the
    # float8 control 0.14-0.32 (generate)
    "limits": {"generate": {"max_gap_sd": 0.08},
               "infer": {"max_gap_sd": 0.08}}}

TINY_MIXES = {
    "tiny_chat": {"plane": "generate", "loop": "open", "rate_per_s": 4.0,
                  "prompt_tokens": {"median": 24, "sigma": 0.5, "min": 8,
                                    "max": 40},
                  "output_tokens": {"median": 8, "sigma": 0.5, "min": 4,
                                    "max": 16},
                  "sampling": {"temperature": 0.8, "top_p": 0.95},
                  "schedule_seed": 1},
    "tiny_batch": {"extends": "tiny_chat", "loop": "closed",
                   "concurrency_per_slot": 2, "sampling": None},
    "tiny_infer": {"plane": "infer", "loop": "open", "rate_per_s": 6.0,
                   "rows": {"min": 1, "max": 4}, "row_tokens": 16,
                   "schedule_seed": 2},
}

TINY_CELLS = {"tiny.chat": "tiny_chat", "tiny.batch": "tiny_batch",
              "tiny.infer": "tiny_infer"}


def make(tmp: Path) -> Path:
    """Copy the benchmark to ``tmp`` and add the tiny files and cells."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(ROOT / "src", tmp / "src")
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tiny-gqa", "source": "test",
                           "file": "bench/configs/tiny-gqa.json",
                           "reduced": [], "why": "CPU rehearsal"})
    for cell, mix in TINY_CELLS.items():
        doc["workloads"].append({"name": cell, "config": "tiny-gqa",
                                 "traffic": mix, "chips": 1,
                                 "why": "CPU rehearsal"})
    like = {"tiny.chat": "danube.chat", "tiny.infer": "danube.infer",
            "tiny.batch": "danube.batch"}
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [c for c, d in like.items()
                               if d in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    (tmp / "bench" / "configs" / "tiny-gqa.json").write_text(
        json.dumps(TINY_CONFIG))
    for name, mix in TINY_MIXES.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    return tmp


def drive(tmp: Path, script: str, timeout: float = 600) -> dict:
    """Run ``script`` (Python, with ``run`` imported from the copy) in a
    fresh CPU process; returns the JSON object it prints last."""
    prelude = ("import sys, json\n"
               f"sys.path.insert(0, {str(tmp / 'bench')!r})\n"
               "import run\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", prelude + script],
                          cwd=tmp, env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
