"""Ensemble + batcher (core/ensemble.py, core/batching.py): device time
per ensemble-forward program, from the trace."""

from harness.programs import ENSEMBLE_FORWARD, device_ns


def read(r):
    got = device_ns(r, ENSEMBLE_FORWARD)
    return None if got is None else got[1] / got[0] / 1e6
