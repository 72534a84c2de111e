"""XLA module names of the served path's programs, as the device trace
names them.  The prefill and the ensemble forward have no stable names
yet: the prefill is a jitted ``functools.partial`` (``jit__unknown``) and
the ensemble forward a closure (``jit__forward_all``)."""

DECODE = "jit_decode_and_sample"
PREFILL = "jit__unknown"
ENSEMBLE_FORWARD = "jit__forward_all"


def device_ns(readings, program: str):
    """(executions, device nanoseconds) of one program in the trace, or
    None where the trace holds none."""
    p = (readings["trace"] or {}).get("programs", {}).get(program)
    if not p or not p["count"]:
        return None
    return p["count"], p["ns"]


def delta(readings, key: str) -> float:
    c0, c1 = readings["counters"]
    return c1[key] - c0[key]
