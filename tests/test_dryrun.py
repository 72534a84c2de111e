"""Dry-run integration: one (arch x shape x mesh) combo per family actually
lowers + compiles against the 512-host-device production mesh, in a
subprocess (so this test process keeps its single CPU device).

The FULL 10x4x2 sweep is run by ``python -m repro.launch.dryrun --all
--both-meshes`` and recorded in EXPERIMENTS.md; here we pin the cheapest
representative combos to keep CI time sane.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMBOS = [
    ("whisper-base", "decode_32k", False),
    ("h2o-danube-1.8b", "decode_32k", True),     # multi-pod proof
    ("rwkv6-1.6b", "long_500k", False),
]


@pytest.mark.parametrize("arch,shape,multi_pod", COMBOS)
def test_dryrun_combo_compiles(arch, shape, multi_pod, tmp_path):
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--out", str(tmp_path)]
    if multi_pod:
        cmd.append("--multi-pod")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=560,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    mesh = "pod2x16x16" if multi_pod else "pod16x16"
    with open(tmp_path / f"{arch}.{shape}.{mesh}.json") as f:
        rec = json.load(f)
    assert rec["status"] == "ok"
    assert rec["n_devices"] == (512 if multi_pod else 256)
    assert rec["device_kind"] == "TPU v5 lite"
    assert rec["cost"]["flops"] > 0
    assert rec["memory"]["argument_bytes"] > 0


def test_roofline_analysis_on_record(tmp_path):
    """Roofline math on a synthetic dry-run record."""
    from repro.analysis import roofline
    rec = {
        "status": "ok", "arch": "yi-9b", "shape": "decode_32k",
        "mesh": "pod16x16", "step": "serve_step", "n_devices": 256,
        "device_kind": "TPU v5 lite",
        "cost": {"flops": 1e9, "bytes_accessed": 1e9},
        "collectives": {"total_bytes": 1e6},
        "memory": {"argument_bytes": 2 * 2 ** 30, "temp_bytes": 2 ** 30,
                   "output_bytes": 2 ** 30, "alias_bytes": 2 ** 30},
    }
    row = roofline.analyze(rec)
    assert row.dominant == "memory"          # 1e9/819e9 > 1e9/197e12
    assert row.fits_hbm is True
    assert 0 < row.useful_ratio < 10
    assert "memory" in roofline.what_would_help(row)


def test_roofline_refuses_unknown_device_kind():
    from repro.analysis import roofline
    assert roofline.peaks_for("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks_for("cpu")
