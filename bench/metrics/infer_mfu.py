"""Whole ensemble step: model FLOPs of the real rows (2 per parameter per
token and causal attention, for every member; padded rows excluded)
over the ensemble programs' device time, as a share of the chip's bf16
peak."""

from harness import counts
from harness.programs import ENSEMBLE_FORWARD, delta, device_ns


def read(r):
    got = device_ns(r, ENSEMBLE_FORWARD)
    rows = delta(r, "rows_total")
    if got is None or not rows:
        return None
    flops = (rows * r["members"]
             * counts.prompt_flops(r["model"], r["mix"]["row_tokens"]))
    return 100.0 * flops / (got[1] * 1e-9) / r["peaks"]["bf16_flops"]
