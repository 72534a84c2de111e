"""The comparison that decides ``correct``.

Generate cells: after the window, a sample drawn from the seed of the
requests that finished, the longest of them always in it, until it holds
``GEN_SAMPLE_TOKENS`` served tokens.  The reference runs once over each
prompt with its served tokens, and at every served position the number
read is how far the served token lies below the reference's choice, in
units of the standard deviation of the reference's logits there:

  greedy     the reference's best logit less the served token's logit;
  sampled    the program draws token j of a request as the argmax of
             logit / T + Gumbel noise over the top-p nucleus, the noise
             drawn from ``fold_in(PRNGKey(request seed), j)``.  The
             reference draws the same noise (``replay_noise``) and reads
             how far its logits would have to move for the served token
             to be its own draw (``_sampled_gap``): below the nucleus
             floor, or outscored by a token well inside the nucleus.

Infer cells: a sample of ``INFER_SAMPLE_ROWS`` answered rows, drawn from
the seed.  For every member, the gap of the class the endpoint returned
among the classes' logits (the first ``num_classes`` of the vocabulary at
the last position), in units of the standard deviation of the
reference's logits there.

Both the widest gap (``max_gap_sd``) and the mean over every compared
position (``mean_gap_sd``) are read.  The reference is the class the
configuration names (``Spec.reference``), passed in by the caller.  With
``control=True`` the same prompts are also run through its control (the
precision below the configuration's; for ``references/dense_gqa.py``
every linear layer in float8), and the gap of the token or class that
the control would choose (by the same rule, with the same noise) is read
the same way, as ``control_max_gap_sd`` and ``control_mean_gap_sd``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import numpy as np

from harness import traffic

GEN_SAMPLE_TOKENS = 1024
GEN_SAMPLE_MAX_REQUESTS = 12
INFER_SAMPLE_ROWS = 512
INFER_BLOCK_ROWS = 64


def _gap(logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """Per row: (best - logit of chosen) / std of the row."""
    rows = np.arange(len(chosen))
    return ((logits.max(-1) - logits[rows, chosen])
            / logits.std(-1))


def _nucleus_floor(logits: np.ndarray, temperature: float, top_p: float
                   ) -> np.ndarray:
    """Per row: the least logit inside the top-p nucleus of
    softmax(logits / T) (the smallest set of most likely tokens whose
    probability reaches ``top_p``)."""
    z = logits.astype(np.float64) / temperature
    order = np.argsort(-z, axis=-1)
    zs = np.take_along_axis(z, order, -1)
    p = np.exp(zs - zs[:, :1])
    cum = np.cumsum(p, -1) / p.sum(-1, keepdims=True)
    last = np.minimum((cum < top_p).sum(-1), z.shape[-1] - 1)
    rows = np.arange(len(z))
    return logits[rows, order[rows, last]]


def sampled_choice(logits: np.ndarray, noise: np.ndarray,
                   temperature: float, top_p: float) -> np.ndarray:
    """The tokens a sampled request draws from these logits and noise:
    the argmax of logit + T * noise over the top-p nucleus."""
    floor = _nucleus_floor(logits, temperature, top_p)
    score = np.where(logits >= floor[:, None],
                     logits + temperature * noise, -np.inf)
    return score.argmax(-1)


def _sampled_gap(logits: np.ndarray, noise: np.ndarray, temperature: float,
                 top_p: float, chosen: np.ndarray) -> np.ndarray:
    """Per row: how far the logits would have to move for ``chosen`` to
    be the reference's draw, in standard deviations of the row's logits;
    0 where it is the draw.  A token outside the reference's nucleus
    reads its distance below the nucleus floor.  Each token of the
    nucleus that outscores ``chosen`` reads the lesser of its lead in
    score and its height above the floor: a token at the nucleus' edge
    leaves it under the least change of the logits, however large its
    noise, so the edge of the nucleus costs what it is worth and no
    more."""
    rows = np.arange(len(chosen))
    floor = _nucleus_floor(logits, temperature, top_p)
    score = logits + temperature * noise
    lead = np.minimum(score - score[rows, chosen][:, None],
                      logits - floor[:, None])
    beaten = np.where(logits >= floor[:, None], lead, -np.inf).max(-1)
    gap = np.maximum(beaten, floor - logits[rows, chosen])
    return np.maximum(gap, 0.0) / logits.std(-1)


@functools.lru_cache(maxsize=None)
def _noise_fn(vocab: int):
    import jax

    @jax.jit
    def noise(key, ctr):
        return jax.vmap(lambda c: jax.random.gumbel(
            jax.random.fold_in(key, c), (vocab,)))(ctr)
    return noise


def replay_noise(seed: int, n: int, vocab: int) -> np.ndarray:
    """The Gumbel noise of a request's first ``n`` sampled tokens, (n,
    vocab) float32: token j's from ``fold_in(PRNGKey(seed), j)``."""
    import jax
    import jax.numpy as jnp
    width = 1 << max(0, n - 1).bit_length()       # one compile per power
    ctr = jnp.arange(width, dtype=jnp.int32)
    key = jax.random.PRNGKey(seed)
    return np.asarray(_noise_fn(vocab)(key, ctr))[:n]


def _stats(prefix: str, gaps: np.ndarray) -> Dict[str, float]:
    return {prefix + "max_gap_sd": float(gaps.max()),
            prefix + "mean_gap_sd": float(gaps.mean())}


def generate_sample(records: List[Dict[str, Any]], reqs, seed: int
                    ) -> List[int]:
    """Indices of the records the check compares."""
    ok = [r for r in records if r.get("status") == 200 and r.get("tokens")]
    if not ok:
        return []
    size = {r["i"]: reqs[r["i"]]["prompt_len"] + len(r["tokens"])
            for r in ok}
    longest = max(ok, key=lambda r: (size[r["i"]], -r["i"]))
    rng = np.random.default_rng([seed, 0xC4EC])
    rest = [r for r in ok if r is not longest]
    rest = [rest[k] for k in rng.permutation(len(rest))]
    picked, n = [longest], len(longest["tokens"])
    for r in rest:
        if n >= GEN_SAMPLE_TOKENS or len(picked) >= GEN_SAMPLE_MAX_REQUESTS:
            break
        picked.append(r)
        n += len(r["tokens"])
    return [r["i"] for r in picked]


def check_generate(reference: type, model: Dict[str, Any],
                   weight_seed: int, reqs, seed: int, records, pad_to: int,
                   sampling: Optional[Dict[str, float]] = None, *,
                   control: bool = False) -> Dict[str, Any]:
    """``reference`` is the configuration's reference class, ``reqs``
    the run's schedule, ``records`` the load generator's records,
    ``sampling`` the mix's (None: greedy); rows are padded to ``pad_to``
    positions (one compile)."""
    by_i = {r["i"]: r for r in records}
    picked = generate_sample(records, reqs, seed)
    if not picked:
        return {"max_gap_sd": None, "tokens_compared": 0}
    refs = [reference(model, weight_seed)]
    if control:
        refs.append(reference(model, weight_seed, control=True))
    rows, lengths, positions, served = [], [], [], []
    for i in picked:
        prompt = traffic.prompt_tokens(seed, i, reqs[i]["prompt_len"],
                                       model["vocab_size"])
        out = np.asarray(by_i[i]["tokens"], np.int32)
        seq = np.concatenate([prompt, out])
        row = np.zeros((pad_to,), np.int32)
        row[:len(seq)] = seq
        rows.append(row)
        lengths.append(len(seq))
        # token j of the output is predicted at position len(prompt)-1+j
        positions.append(list(range(len(prompt) - 1, len(seq) - 1)))
        served.append(out)
    blocks = [(row[None], np.asarray([n], np.int32))
              for row, n in zip(rows, lengths)]
    pos = [[p] for p in positions]
    per_ref = [[blk[0] for blk in ref.logits(blocks, pos)] for ref in refs]
    gaps = {"program": [], "control": []}
    for b, i in enumerate(picked):
        truth = per_ref[0][b]
        if sampling:
            t, top_p = sampling["temperature"], sampling["top_p"]
            noise = replay_noise(reqs[i]["seed"], len(served[b]),
                                 model["vocab_size"])
            gaps["program"].append(_sampled_gap(truth, noise, t, top_p,
                                                served[b]))
            if control:
                pick = sampled_choice(per_ref[1][b], noise, t, top_p)
                gaps["control"].append(_sampled_gap(truth, noise, t, top_p,
                                                    pick))
        else:
            gaps["program"].append(_gap(truth, served[b]))
            if control:
                gaps["control"].append(_gap(truth, per_ref[1][b].argmax(-1)))
    out = {**_stats("", np.concatenate(gaps["program"])),
           "tokens_compared": int(sum(len(s) for s in served)),
           "requests_compared": len(picked)}
    if control:
        out.update(_stats("control_", np.concatenate(gaps["control"])))
    return out


def check_infer(reference: type, model: Dict[str, Any], members: int,
                weight_seed: int, reqs, row_tokens: int, seed: int,
                records, num_classes: int, *, control: bool = False
                ) -> Dict[str, Any]:
    """``reference`` is the configuration's reference class; member m's
    weights come from ``weight_seed + m``."""
    rng = np.random.default_rng([seed, 0xC4EC])
    answered = [r for r in records
                if r.get("status") == 200 and r.get("classes")]
    rows: List[tuple] = []                    # (request i, row, classes)
    for r in [answered[k] for k in rng.permutation(len(answered))]:
        toks = traffic.prompt_tokens(seed, r["i"],
                                     (reqs[r["i"]]["rows"], row_tokens),
                                     model["vocab_size"])
        for j in range(len(toks)):
            rows.append((toks[j], [r["classes"][f"model_{m}"][j]
                                   for m in range(members)]))
        if len(rows) >= INFER_SAMPLE_ROWS:
            break
    rows = rows[:INFER_SAMPLE_ROWS]
    if not rows:
        return {"max_gap_sd": None, "rows_compared": 0}
    toks = np.stack([t for t, _ in rows])
    S = toks.shape[1]
    gaps = {"program": [], "control": []}
    for m in range(members):
        refs = [reference(model, weight_seed + m)]
        if control:
            refs.append(reference(model, weight_seed + m, control=True))
        served = np.asarray([int(c[m].split("_")[-1]) for _, c in rows])
        starts = list(range(0, len(rows), INFER_BLOCK_ROWS))
        blocks = [(toks[lo:lo + INFER_BLOCK_ROWS],
                   np.full((len(toks[lo:lo + INFER_BLOCK_ROWS]),), S,
                           np.int32)) for lo in starts]
        pos = [[[S - 1]] * len(t) for t, _ in blocks]
        per_ref = [ref.logits(blocks, pos) for ref in refs]
        for k, lo in enumerate(starts):
            outs = [np.stack(r[k])[:, 0] for r in per_ref]
            n = len(outs[0])
            sd = outs[0].std(-1)
            cls = outs[0][:, :num_classes]
            rows_i = np.arange(n)
            gaps["program"].append(
                (cls.max(-1) - cls[rows_i, served[lo:lo + n]]) / sd)
            if control:
                pick = outs[1][:, :num_classes].argmax(-1)
                gaps["control"].append((cls.max(-1) - cls[rows_i, pick])
                                       / sd)
    out = {**_stats("", np.concatenate(gaps["program"])),
           "rows_compared": len(rows) * members}
    if control:
        out.update(_stats("control_", np.concatenate(gaps["control"])))
    return out
