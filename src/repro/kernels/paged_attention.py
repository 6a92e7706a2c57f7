"""Paged-attention decode Pallas kernel: one query token per sequence
against a block-paged KV cache (DESIGN.md §8).

The cache is a flat pool of fixed-size pages ``(num_pages, block_size,
Hkv, d)``, viewed as ``(num_pages, block_size, Hkv*d)`` so that one kv-head
of one page is a ``(block_size, d)`` tile the TPU can DMA.  Each sequence
owns an int32 block-table row mapping its logical KV blocks to pool pages.  Both the table ``(B, M)`` and the inclusive
context positions ``(B,)`` ride in through
``pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=2)`` so the k/v
BlockSpec index maps can chase ``tab[b, j]`` — page indirection costs a
scalar lookup at grid-index time, not a gather in the kernel body.

TPU-native design mirrors ``flash_attention.py``:
  * grid (B, Hkv, M) with the block axis innermost ("arbitrary") carrying
    online-softmax state (m/l lane-replicated, acc (G, d)) in VMEM,
  * whole irrelevant pages are SKIPPED via ``pl.when`` — a sequence at
    context length c touches ceil((c+1)/bs) pages, not M,
  * GQA is laid out as (B, Hkv, G, d) queries so each page is fetched once
    per kv-head and hit by all G query heads on the MXU,
  * optional sliding window (page skip + in-page mask) and logit softcap.

Validated in interpret mode on CPU against ``ref.paged_attention``;
compiled on real TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF


def _paged_kernel(
    tab_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, window, softcap, bs, num_blocks,
):
    b = pl.program_id(0)
    j = pl.program_id(2)  # logical kv block (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = ctx_ref[b]  # inclusive current position: valid kpos <= ctx
    relevant = j * bs <= ctx
    if window is not None:
        relevant &= j * bs + bs - 1 >= ctx - window + 1

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, d)
        k = k_ref[0].astype(jnp.float32)  # (bs, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (G, bs)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= ctx
        if window is not None:
            mask &= (ctx - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_blocks - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention(
    q, k_pages, v_pages, block_tables, context_lens,
    *, scale=None, window=None, softcap=None, interpret: bool = True,
):
    """Single-token decode over a paged KV pool.

    q: (B, Hkv, G, d) current-position queries; k_pages/v_pages:
    (num_pages, block_size, Hkv, d); block_tables: (B, M) int32 page ids;
    context_lens: (B,) int32 INCLUSIVE current position (the token being
    decoded sits at kpos == context_lens[b], already written to its page).
    Returns (B, Hkv, G, d).
    """
    B, Hkv, G, d = q.shape
    P, bs, _, _ = k_pages.shape
    M = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _paged_kernel,
        scale=scale, window=window, softcap=softcap, bs=bs, num_blocks=M,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, M),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, h, j, tab, ctx: (b, h, 0, 0)),
            # the indirection: logical block j of sequence b lives at page
            # tab[b, j] — resolved in the index map from the prefetched table;
            # kv-head h is lane block h of the (bs, Hkv*d) page view
            pl.BlockSpec((1, bs, d), lambda b, h, j, tab, ctx: (tab[b, j], 0, h)),
            pl.BlockSpec((1, bs, d), lambda b, h, j, tab, ctx: (tab[b, j], 0, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d), lambda b, h, j, tab, ctx: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((G, 128), jnp.float32),  # l
            pltpu.VMEM((G, d), jnp.float32),  # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        block_tables, context_lens, q,
        k_pages.reshape(P, bs, Hkv * d), v_pages.reshape(P, bs, Hkv * d),
    )
