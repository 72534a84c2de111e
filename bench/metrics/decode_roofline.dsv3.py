"""Whole MLA + MoE decode step vs roofline: the least bytes a step must
read (``counts_mla_moe.decode_step_bytes``: every weight but the
embedding, the held experts at their expected hit share for the active
rows, the head, and the live latent cache) over the chip's HBM
bandwidth, as a share of the decode program's device time.  Active rows
and contexts are time-averages over the traced window."""

from harness import counts_mla_moe
from harness.programs import DECODE, device_ns


def read(r):
    got = device_ns(r, DECODE)
    live = r["live"]
    if got is None or not live or not live["active"]:
        return None
    least = counts_mla_moe.decode_step_bytes(r["model"], live["active"],
                                             live["context"])
    per_step_s = got[1] / got[0] * 1e-9
    return 100.0 * least / r["peaks"]["hbm_bytes_per_s"] / per_step_s
