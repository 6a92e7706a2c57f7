"""The one traffic generator: turns a mix's data file (``traffic/<mix>.json``)
and a run's seed into the requests or batches of that run.

Every seed gets the same work.  A serving mix is laid out in blocks of
``block_requests`` requests; each block holds the same prompt lengths,
output lengths and gaps between arrivals, taken at evenly spaced quantiles
of the mix's distributions.  The mix's ``layout_seed`` pairs them up and
orders them inside each block, a different order in each block; the run's
seed draws the prompt tokens (and, elsewhere, the weights).  The order is
not the run's: at four fifths of the knee the tail of time to first token
depends on which long requests arrive together, and a seed that moved them
would move the tail by more than any change a PR makes (PERF.md).
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(q: np.ndarray, median: float, sigma: float) -> np.ndarray:
    z = np.asarray([NormalDist().inv_cdf(float(p)) for p in q])
    return median * np.exp(sigma * z)


def block_layout(mix: dict):
    """(prompt lengths, output lengths, gaps in ticks) of one block, before
    the seed orders them."""
    n = int(mix["block_requests"])
    q = _quantiles(n)
    p = mix["prompt"]
    raw = _lognormal(q, p["median"], p["sigma"])
    buckets = np.asarray(sorted(p["buckets"]))
    # round up to the next bucket; the longest bucket takes the tail
    idx = np.minimum(np.searchsorted(buckets, raw), len(buckets) - 1)
    prompts = buckets[idx].astype(np.int64)
    o = mix["output"]
    outs = np.clip(np.round(_lognormal(q, o["median"], o["sigma"])), o["min"], o["max"])
    rate = float(mix["arrival"]["rate_per_tick"])
    gaps = -np.log1p(-q) / rate  # exponential quantiles: Poisson arrivals
    rng = np.random.default_rng(int(mix["layout_seed"]))
    # one fixed pairing decorrelates the sorted quantile columns
    return prompts[rng.permutation(n)], outs.astype(np.int64)[rng.permutation(n)], gaps


def serve_requests(mix: dict, seed: int, vocab: int, until_tick: int):
    """Requests arriving before ``until_tick`` on the engine's tick clock:
    a list of (rid, prompt int32 array, max_new, arrival_tick)."""
    prompts, outs, gaps = block_layout(mix)
    n = len(prompts)
    layout = np.random.default_rng(np.random.SeedSequence([int(mix["layout_seed"]), 1]))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5E7E]))
    out, t, rid = [], 0.0, 0
    while True:
        order, gap_order = layout.permutation(n), layout.permutation(n)
        for j in range(n):
            arrival = int(math.floor(t))
            if arrival >= until_tick:
                return out
            L, m = int(prompts[order[j]]), int(outs[order[j]])
            tokens = rng.integers(0, vocab, size=L, dtype=np.int64).astype(np.int32)
            out.append((rid, tokens, m, arrival))
            rid += 1
            t += gaps[gap_order[j]]


def token_batch(data_key, step, chain, batch: int, seq: int, vocab: int):
    """One chain's minibatch at ``step``: ``batch`` rows of ``seq`` tokens
    and their next-token labels, from a stream keyed by (step, chain), made
    on the device.  Traced inside the sampler's step."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.fold_in(data_key, step), chain)
    toks = jax.random.randint(key, (batch, seq + 1), 0, vocab, jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
