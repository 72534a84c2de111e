"""Model step: MLA + MoE prefill programs' device time per 1000 real
prompt tokens (padding excluded) over the traced window."""

from harness.programs import PREFILL, delta, device_ns


def read(r):
    got = device_ns(r, PREFILL)
    tokens = delta(r, "prefill_tokens_total")
    if got is None or not tokens:
        return None
    return got[1] / 1e6 / (tokens / 1000.0)
