"""Flexible batching (paper §2.3), TPU-native.

The paper's Flask server accepts any client batch size for free because
PyTorch graphs are dynamic.  XLA requires static shapes, so FlexServe-JAX
realizes "flexible batch sizes" with *bucketing*: a client batch of n
samples is padded up to the smallest configured bucket >= n and executed
under a jit specialization for that bucket.  The jit cache is therefore
bounded by len(buckets) — O(log maxB) with power-of-two buckets — while
clients see fully variable batch sizes, and padded rows are masked out of
the response.

Sequence lengths bucket the same way for text serving (pad-to-bucket with
per-row valid lengths).
"""

from __future__ import annotations

import bisect
import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class BucketSpec:
    """Monotone bucket sizes; default powers of two up to max_size."""

    sizes: Tuple[int, ...]

    @staticmethod
    def pow2(max_size: int, min_size: int = 1) -> "BucketSpec":
        sizes, s = [], min_size
        while s < max_size:
            sizes.append(s)
            s *= 2
        sizes.append(max_size)
        return BucketSpec(tuple(sizes))

    def bucket_for(self, n: int) -> int:
        if n > self.sizes[-1]:
            raise ValueError(f"batch of {n} exceeds max bucket "
                             f"{self.sizes[-1]}")
        idx = bisect.bisect_left(self.sizes, n)
        return self.sizes[idx]


def pad_to(arr: np.ndarray, n: int, axis: int = 0, fill=0):
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, n - arr.shape[axis])
    return np.pad(arr, pad, constant_values=fill)


def pad_batch(batch: Dict[str, np.ndarray], bucket: int,
              axis: int = 0) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """Pad every array in ``batch`` to ``bucket`` rows; returns (padded, mask)."""
    n = next(iter(batch.values())).shape[axis]
    padded = {k: pad_to(np.asarray(v), bucket, axis) for k, v in batch.items()}
    mask = np.arange(bucket) < n
    return padded, mask


class FlexibleBatcher:
    """Wraps a batch-polymorphic function with bucketed jit dispatch.

    fn(batch_dict, *args) -> pytree with leading batch axis.  Calls with
    ANY batch size n <= max bucket; output is sliced back to n rows.
    ``args`` (model params) pass through unpadded as jit ARGUMENTS: a
    closure over them would bake every weight into the program as a
    constant.  Tracks per-bucket compilation, proving the jit cache stays
    bounded.
    """

    def __init__(self, fn: Callable, buckets: BucketSpec,
                 donate: bool = False):
        self.donate = donate
        self._fn = jax.jit(fn, donate_argnums=(0,) if donate else ())
        self.buckets = buckets
        self.calls = 0
        self.compiles: Dict[int, int] = {}

    def _cache_size(self) -> Optional[int]:
        probe = getattr(self._fn, "_cache_size", None)
        return probe() if callable(probe) else None

    def __call__(self, batch: Dict[str, Any], *args):
        n = next(iter(batch.values())).shape[0]
        bucket = self.buckets.bucket_for(n)
        padded, _mask = pad_batch(batch, bucket)
        self.calls += 1
        before = self._cache_size()
        out = self._fn(padded, *args)
        after = self._cache_size()
        if before is None or after is None:
            # no cache introspection on this jax — fall back to first-call
            self.compiles.setdefault(bucket, 1)
        elif after > before:
            # a real jit cache miss: this call traced + compiled
            self.compiles[bucket] = self.compiles.get(bucket, 0) \
                + (after - before)
        return jax.tree_util.tree_map(lambda t: t[:n], out)

    @property
    def num_compilations(self) -> int:
        return sum(self.compiles.values())

    def warm(self, example_batch: Dict[str, Any], *args,
             buckets: Optional[Sequence[int]] = None) -> float:
        """Pre-compile bucket specializations off the hot path.

        Pads ``example_batch`` (any row count) up to each requested bucket
        and runs the jitted fn, so a later swap-in serves every bucket from
        a warm jit cache instead of paying compile latency on live traffic.
        Returns wall-clock seconds spent warming.
        """
        t0 = time.perf_counter()
        example = {k: np.asarray(v) for k, v in example_batch.items()}
        n = next(iter(example.values())).shape[0]
        for b in (buckets if buckets is not None else self.buckets.sizes):
            # exactly b rows -> bucket_for(b) == b: one compile per bucket
            batch = {k: (v[:b] if n >= b else pad_to(v, b))
                     for k, v in example.items()}
            jax.block_until_ready(self(batch, *args))
        return time.perf_counter() - t0


def pad_sequences(seqs: Sequence[Sequence[int]], bucket_spec: BucketSpec,
                  pad_id: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Right-pad variable-length token sequences to a length bucket.

    Returns (tokens (B, S_bucket) int32, lengths (B,) int32)."""
    maxlen = max(len(s) for s in seqs)
    S = bucket_spec.bucket_for(maxlen)
    tokens = np.full((len(seqs), S), pad_id, np.int32)
    lengths = np.zeros((len(seqs),), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = np.asarray(s, np.int32)
        lengths[i] = len(s)
    return tokens, lengths
