"""MLA + MoE prefill vs peak: 2 FLOPs per active linear parameter
(``counts_mla_moe.active_linear_params``: attention projections, dense
MLPs, shared experts and the held experts a token routes to on average)
per real prompt token, over the prefill programs' device time, as a
share of the chip's bf16 peak.  The quadratic attention term, the
routers and the head are left out: a lower bound."""

from harness import counts_mla_moe
from harness.programs import PREFILL, delta, device_ns


def read(r):
    got = device_ns(r, PREFILL)
    tokens = delta(r, "prefill_tokens_total")
    if got is None or not tokens:
        return None
    flops = 2 * counts_mla_moe.active_linear_params(r["model"]) * tokens
    return 100.0 * flops / (got[1] * 1e-9) / r["peaks"]["bf16_flops"]
