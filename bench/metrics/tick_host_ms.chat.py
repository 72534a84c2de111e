"""Scheduler (core/scheduler.py): host milliseconds per decode tick that
are neither the device step nor prefill, from the scheduler's lifetime
counters (host clock of host work)."""

from harness.programs import delta


def read(r):
    ticks = delta(r, "ticks")
    return delta(r, "host_ms_total") / ticks if ticks else None
