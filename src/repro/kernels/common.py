"""Shared kernel utilities."""

from __future__ import annotations

import functools

import jax

NEG_INF = -1e30


@functools.cache
def default_interpret() -> bool:
    """Interpret Pallas kernels unless running on a real TPU."""
    return jax.default_backend() != "tpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
