"""Fused BMA mixture + token selection Pallas kernel.

The engine's decode epilogue reads the (K, S, V) member-logit tensor three
times on the unfused path: per-member log-softmax, the K-mixture reduce,
then temperature/top-k selection.  This kernel does all of it in ONE pass
per slot — each grid step pulls one slot's K logit rows into VMEM and emits
the mixture log-prob row plus the selected token, so the K-member ensemble
pays a single memory pass per decoded token.  A vocab row is held as
(rows, 128) lane tiles, so a 151,936-entry vocabulary fills whole vregs
instead of one padded sublane per row.

Exact-equivalence contract (pinned in tests/test_paged_attention.py):
  * mixture rows match ``serve.engine.bma.mixture_logprobs`` (f32 math,
    both "probs" and "logprobs" modes);
  * greedy tokens match ``jnp.argmax`` (first-occurrence tie-break);
  * sampled tokens match ``jax.random.categorical`` EXACTLY given the same
    key, because categorical is argmax(logits + Gumbel) and the caller
    passes in the identical ``jax.random.gumbel(key, (S, V), f32)`` draw
    (the kernel only fuses the mask/add/argmax);
  * top-k keeps ties at the k-th-largest threshold, like
    ``sampling._top_k_mask``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF

LANES = 128


def _reduce(fn, x):
    """Reduce the trailing (rows, lanes) vocab tile to (..., 1, 1), one
    axis at a time (lanes, then rows)."""
    return fn(fn(x, axis=-1, keepdims=True), axis=-2, keepdims=True)


def _vocab_index(shape):
    """Flat vocab index of every element of a (..., rows, LANES) tile."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return rows * LANES + lanes


def _first_argmax(x):
    """(rows, LANES) f32 -> (1, 1) int32 flat index of the first maximum
    (jnp.argmax tie-break), via an iota-min trick that lowers to TPU
    reductions."""
    idx = _vocab_index(x.shape)
    hit = x == _reduce(jnp.max, x)
    return _reduce(jnp.min, jnp.where(hit, idx, x.size))


def _log_softmax(x):
    m = _reduce(jnp.max, x)
    return x - (m + jnp.log(_reduce(jnp.sum, jnp.exp(x - m))))


def _bma_select_kernel(logits_ref, *refs, mode, temperature, top_k):
    """One slot: logits (K, 1, rows, LANES) -> mixture log-probs
    (1, rows, LANES) and the token, lane-broadcast into (1, 1, LANES)."""
    *noise, logp_ref, tok_ref = refs  # noise: the Gumbel row when sampling
    x = logits_ref[:, 0].astype(jnp.float32)  # (K, rows, LANES)
    K = x.shape[0]
    lp = _log_softmax(x)  # per member
    if mode == "probs":  # logsumexp over members - log K
        mk = jnp.max(lp, axis=0)  # (rows, LANES)
        mix = mk + jnp.log(jnp.sum(jnp.exp(lp - mk), axis=0))
        mix = mix - jnp.log(jnp.float32(K))
    else:  # "logprobs": renormalized mean log-prob
        mix = _log_softmax(jnp.mean(lp, axis=0))
    logp_ref[0] = mix

    sel = mix
    if temperature > 0.0:
        sel = mix / jnp.float32(temperature)
        if top_k:
            k = min(int(top_k), sel.size)
            idx = _vocab_index(sel.shape)

            def strike(_, masked):
                # remove ONE occurrence of the current max so duplicates count
                # toward k, exactly like lax.top_k's sorted tail
                return jnp.where(idx == _first_argmax(masked), NEG_INF, masked)

            masked = jax.lax.fori_loop(0, k - 1, strike, sel)
            thresh = _reduce(jnp.max, masked)  # k-th largest
            sel = jnp.where(sel < thresh, NEG_INF, sel)  # ties at thresh kept
        (gumbel_ref,) = noise
        sel = sel + gumbel_ref[0].astype(jnp.float32)
    tok_ref[0] = jnp.broadcast_to(_first_argmax(sel), (1, LANES))


def bma_select(
    logits, gumbel, *, mode: str, temperature: float, top_k: int,
    interpret: bool = True,
):
    """logits (K, S, V), gumbel (S, V) f32 (unused, and may be None, when
    temperature <= 0) -> (tokens (S,) int32, mixture log-probs (S, V) f32).

    The vocabulary is laid out as (rows, LANES) tiles: V pads to a multiple
    of LANES with NEG_INF logits (zero Gumbel), which no selection can pick
    and which are sliced off the returned log-probs.  Each grid step holds
    one slot's K member rows, its Gumbel row and its output row in VMEM."""
    K, S, V = logits.shape
    pad = (-V) % LANES
    rows = (V + pad) // LANES
    logits = jnp.pad(logits, ((0, 0), (0, 0), (0, pad)), constant_values=NEG_INF)
    operands = [logits.reshape(K, S, rows, LANES)]
    in_specs = [pl.BlockSpec((K, 1, rows, LANES), lambda s: (0, s, 0, 0))]
    if temperature > 0.0:
        operands.append(jnp.pad(gumbel, ((0, 0), (0, pad))).reshape(S, rows, LANES))
        in_specs.append(pl.BlockSpec((1, rows, LANES), lambda s: (s, 0, 0)))
    kernel = functools.partial(
        _bma_select_kernel,
        mode=mode, temperature=float(temperature), top_k=int(top_k),
    )
    logp, tok = pl.pallas_call(
        kernel,
        grid=(S,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, rows, LANES), lambda s: (s, 0, 0)),
            pl.BlockSpec((1, 1, LANES), lambda s: (s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((S, 1, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)
    return tok[:, 0, 0], logp.reshape(S, rows * LANES)[:, :V]
