"""Model step (core/engine.py, models/transformer.py): device time per
fused decode program of the MLA + MoE model, from the trace."""

from harness.programs import DECODE, device_ns


def read(r):
    got = device_ns(r, DECODE)
    return None if got is None else got[1] / got[0] / 1e6
