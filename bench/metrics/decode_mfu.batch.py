"""Whole decode step vs peak: model FLOPs of the active slots (2 per
matmul parameter each, plus attention over each slot's live context)
over the decode programs' device time, as a share of the chip's bf16
peak.  Active slots and contexts are time-averages over the traced
window from the streams' token arrivals."""

from harness import counts
from harness.programs import DECODE, device_ns


def read(r):
    got = device_ns(r, DECODE)
    live = r["live"]
    if got is None or not live or not live["active"]:
        return None
    m = r["model"]
    flops = (live["active"] * 2 * counts.matmul_params(m)
             + counts.attention_flops(m, 1) * live["context"])
    per_step_s = got[1] / got[0] * 1e-9
    return 100.0 * flops / per_step_s / r["peaks"]["bf16_flops"]
