"""Traffic schedules from a mix file and a seed.  Never imports JAX.

A mix is a data file under ``bench/traffic/``.  One general generator
reads every mix:

  plane          "generate" (streamed /v1/generate) or "infer" (/v1/infer)
  loop           "open": requests are due on a fixed schedule whatever the
                 server does; "closed": ``concurrency`` clients each send
                 their next request when the last one ends
  rate_per_s     open loop: mean arrival rate (Poisson gaps)
  concurrency    closed loop: clients, or ``concurrency_per_slot`` times
                 the configuration's decode slots
  prompt_tokens, output_tokens   generate: {"median", "sigma", "min",
                 "max"} of a lognormal, clipped to [min, max]
  rows, row_tokens               infer: rows per request from 1..max with
                 P(r) proportional to 1/r; every row has row_tokens tokens
  sampling       generate: {"temperature", "top_p"} of every request;
                 without it every request is decoded greedily
  schedule_seed  fixes the arrivals and the sizes: every --seed offers the
                 same requests at the same times
  extends        name of another mix whose keys this one starts from

The --seed draws the token ids of every prompt and each request's
sampling seed; the weights are drawn from it too (``run.py``).
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

# requests a closed loop may draw on: more than any run can finish
CLOSED_LOOP_REQUESTS = 4096


def load_mix(traffic_dir: Path, name: str) -> Dict[str, Any]:
    """The mix ``name`` with every ``extends`` resolved."""
    seen = []
    mix: Dict[str, Any] = {}
    while name is not None:
        if name in seen:
            raise ValueError(f"mix {name!r} extends itself")
        seen.append(name)
        raw = json.loads((traffic_dir / f"{name}.json").read_text())
        mix = {**raw, **mix}
        name = raw.get("extends")
    mix.pop("extends", None)
    mix["name"] = seen[0]
    return mix


def _lognormal(rng, spec: Dict[str, Any], n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _rows(rng, spec: Dict[str, Any], n: int) -> np.ndarray:
    r = np.arange(spec["min"], spec["max"] + 1)
    p = (1.0 / r) / (1.0 / r).sum()
    return rng.choice(r, size=n, p=p)


def concurrency(mix: Dict[str, Any], num_slots: int) -> int:
    if "concurrency" in mix:
        return int(mix["concurrency"])
    return int(mix["concurrency_per_slot"] * num_slots)


def schedule(mix: Dict[str, Any], seed: int, seconds: float
             ) -> List[Dict[str, Any]]:
    """The requests of one run, in the order they are due.

    Each entry has ``i`` and, for an open loop, ``due_s`` (seconds after
    the window opens: a Poisson process over the window); generate
    entries have ``prompt_len``, ``max_new_tokens`` and ``seed``, infer
    entries ``rows``.  Arrivals and sizes come from the mix's
    ``schedule_seed`` alone, in a fixed order; ``seed`` draws only the
    sampling seeds here and the prompt tokens in :func:`prompt_tokens`."""
    fixed = np.random.default_rng(mix["schedule_seed"])
    if mix["loop"] == "open":
        rate = mix["rate_per_s"]
        gaps = fixed.exponential(1.0 / rate, int(2 * rate * seconds) + 64)
        due = np.concatenate([[0.0], np.cumsum(gaps)])
        due = due[due < seconds]
        reqs: List[Dict[str, Any]] = [{"i": i, "due_s": float(d)}
                                      for i, d in enumerate(due)]
    else:
        reqs = [{"i": i} for i in range(CLOSED_LOOP_REQUESTS)]
    n = len(reqs)
    if mix["plane"] == "generate":
        plen = _lognormal(fixed, mix["prompt_tokens"], n)
        olen = _lognormal(fixed, mix["output_tokens"], n)
        seeds = np.random.default_rng([seed, 0x5EED]).integers(
            0, 2 ** 31 - 1, n)
        for r, p, o, s in zip(reqs, plen, olen, seeds):
            r.update(prompt_len=int(p), max_new_tokens=int(o), seed=int(s))
    else:
        for r, k in zip(reqs, _rows(fixed, mix["rows"], n)):
            r["rows"] = int(k)
    return reqs


def prompt_tokens(seed: int, i: int, shape, vocab: int) -> np.ndarray:
    """Token ids of request ``i``: the same for the same (seed, i)."""
    rng = np.random.default_rng([seed, 0x70C, i])
    return rng.integers(1, vocab, shape, dtype=np.int64).astype(np.int32)


def payload(mix: Dict[str, Any], req: Dict[str, Any], seed: int,
            vocab: int) -> Dict[str, Any]:
    """The JSON body of one request."""
    if mix["plane"] == "generate":
        body = {"prompts": [prompt_tokens(seed, req["i"], req["prompt_len"],
                                          vocab).tolist()],
                "max_new_tokens": req["max_new_tokens"], "stream": True}
        if mix.get("sampling"):
            body.update(temperature=mix["sampling"]["temperature"],
                        top_p=mix["sampling"]["top_p"], seed=req["seed"])
        return body
    rows = prompt_tokens(seed, req["i"], (req["rows"], mix["row_tokens"]),
                         vocab)
    return {"inputs": {"tokens": rows.tolist()},
            "policy": mix.get("policy", "soft_vote")}
