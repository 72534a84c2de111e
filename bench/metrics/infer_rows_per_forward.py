"""Coalescer (serving/coalesce.py): rows per ensemble forward over the
traced window, from the coalescer's lifetime counters."""

from harness.programs import delta


def read(r):
    batches = delta(r, "batches_formed")
    return delta(r, "rows_total") / batches if batches else None
