"""Scheduler (core/scheduler.py): share of the decode slots that carried
a token, over the traced window: decode-step tokens / (ticks x slots),
from the scheduler's lifetime counters.  ``decode_tokens_total`` also
counts each request's first token, which its prefill makes, so one per
admitted request comes off.  The counters are read at the window's two
edges, where a tick may be counted before its tokens: about one tick in
the ~200 of a traced window."""

from harness.programs import delta


def read(r):
    ticks = delta(r, "ticks")
    if not ticks:
        return None
    step_tokens = delta(r, "decode_tokens_total") - delta(r,
                                                         "prefill_requests")
    return 100.0 * step_tokens / (ticks * r["num_slots"])
