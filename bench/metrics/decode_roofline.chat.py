"""Whole decode step vs roofline: the least bytes a step must read (the
engine's weights once, and the live keys and values of the active slots)
over the chip's HBM bandwidth, as a share of the decode program's device
time.  Contexts are time-averages over the traced window."""

from harness import counts
from harness.programs import DECODE, device_ns


def read(r):
    got = device_ns(r, DECODE)
    live = r["live"]
    if got is None or not live or not live["active"]:
        return None
    m = r["model"]
    least = (counts.weight_bytes(m)
             + counts.kv_bytes_per_token(m) * live["context"])
    per_step_s = got[1] / got[0] * 1e-9
    return 100.0 * least / r["peaks"]["hbm_bytes_per_s"] / per_step_s
