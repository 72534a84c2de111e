"""Three-term roofline analysis from dry-run artifacts.

    compute term    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory term     = HLO_bytes_per_chip / HBM_bw
    collective term = collective_bytes_per_chip / link_bw

The peaks are those of the record's ``device_kind`` (``PEAKS``); a device
kind missing from the table is an error, never a default.

``compiled.cost_analysis()`` reports the PER-DEVICE program (the SPMD
partition), so terms divide by per-chip peaks directly.  Collective bytes
are parsed from the optimized HLO (operand sizes of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute).

MODEL_FLOPS uses 6·N·D (training) or 2·N·D (inference forward) with
N = active params and D = processed tokens, divided by chips — the
"useful compute" yardstick against which HLO_FLOPs reveals remat/dispatch
overhead.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from repro.configs import get_config, get_shape


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks of one accelerator."""
    flops: float             # bf16 FLOP/s
    hbm_bw: float            # HBM bytes/s
    hbm_bytes: int           # HBM capacity
    ici_bw: float            # bytes/s of one chip-to-chip link


# Keyed by jax's ``device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
# 1,600 Gbit/s of interconnect per chip (50 GB/s on each of 4 links).
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16 * 2 ** 30,
                         ici_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    step: str
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_per_chip: float
    hlo_flops_per_chip: float
    useful_ratio: float
    fits_hbm: Optional[bool]
    bytes_per_chip: Optional[int]
    raw: Dict[str, Any]

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> Dict[str, Any]:
        d = self.__dict__.copy()
        d.pop("raw")
        return d


def model_flops(arch: str, shape_name: str) -> float:
    """Useful FLOPs for one step of this (arch, shape), whole program."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per row
    return 2.0 * n_active * shape.global_batch


def analyze(record: Dict[str, Any]) -> Optional[RooflineRow]:
    if record.get("status") != "ok":
        return None
    n_dev = record["n_devices"]
    flops_chip = float(record["cost"]["flops"] or 0.0)
    bytes_chip = float(record["cost"]["bytes_accessed"] or 0.0)
    coll_chip = float(record["collectives"]["total_bytes"] or 0.0)

    peaks = peaks_for(record["device_kind"])
    compute_s = flops_chip / peaks.flops
    memory_s = bytes_chip / peaks.hbm_bw
    collective_s = coll_chip / peaks.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    mf_chip = model_flops(record["arch"], record["shape"]) / n_dev
    useful = mf_chip / flops_chip if flops_chip else 0.0

    mem = record.get("memory", {})
    per_chip = None
    fits = None
    if mem.get("argument_bytes") is not None:
        per_chip = (mem["argument_bytes"] + (mem.get("temp_bytes") or 0)
                    + (mem.get("output_bytes") or 0)
                    - (mem.get("alias_bytes") or 0))
        fits = per_chip <= peaks.hbm_bytes

    return RooflineRow(
        arch=record["arch"], shape=record["shape"], mesh=record["mesh"],
        step=record.get("step", "?"),
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops_per_chip=mf_chip,
        hlo_flops_per_chip=flops_chip, useful_ratio=useful,
        fits_hbm=fits, bytes_per_chip=per_chip, raw=record)


def load_results(dir_path: str) -> List[Dict[str, Any]]:
    out = []
    for name in sorted(os.listdir(dir_path)):
        if name.endswith(".json"):
            with open(os.path.join(dir_path, name)) as f:
                out.append(json.load(f))
    return out


def _fmt_s(x: float) -> str:
    if x >= 1.0:
        return f"{x:8.2f}s "
    if x >= 1e-3:
        return f"{x * 1e3:8.2f}ms"
    return f"{x * 1e6:8.1f}us"


def table(rows: List[RooflineRow], mesh: Optional[str] = None) -> str:
    hdr = (f"{'arch':26s} {'shape':12s} {'step':12s} "
           f"{'compute':10s} {'memory':10s} {'collect':10s} "
           f"{'dominant':10s} {'useful':7s} {'GiB/chip':9s} fits")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        if mesh and r.mesh != mesh:
            continue
        gib = (f"{r.bytes_per_chip / 2**30:8.2f}" if r.bytes_per_chip
               else "       ?")
        lines.append(
            f"{r.arch:26s} {r.shape:12s} {r.step:12s} "
            f"{_fmt_s(r.compute_s)} {_fmt_s(r.memory_s)} "
            f"{_fmt_s(r.collective_s)} {r.dominant:10s} "
            f"{r.useful_ratio:6.1%} {gib} "
            f"{'Y' if r.fits_hbm else 'N' if r.fits_hbm is not None else '?'}")
    return "\n".join(lines)


def what_would_help(row: RooflineRow) -> str:
    """One-sentence lever on the dominant term (used in EXPERIMENTS.md)."""
    if row.dominant == "compute":
        if row.useful_ratio < 0.5:
            return ("compute-bound with low useful ratio: cut remat "
                    "recompute / MoE capacity slack before touching layout")
        return "compute-bound near-useful: increase arithmetic intensity "\
               "(fusion, larger tiles) or add chips"
    if row.dominant == "memory":
        return ("memory-bound: shrink bytes touched — windowed/ring KV "
                "cache, bf16 states, fused kernels that keep tiles in VMEM")
    return ("collective-bound: reshard to cut cross-chip traffic — e.g. "
            "batch-only sharding for small tensors, expert-parallel "
            "all-to-all instead of weight all-gather, overlap collectives")


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)
    rows = [r for r in (analyze(rec) for rec in load_results(args.dir))
            if r is not None]
    print(table(rows, mesh=args.mesh))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump([r.as_dict() for r in rows], f, indent=1)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
