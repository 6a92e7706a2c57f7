"""Paged-attention decode Pallas kernel: one query token per sequence
against a block-paged KV cache (DESIGN.md §8).

The cache is a flat pool of fixed-size pages ``(num_pages, block_size,
Hkv, d)``.  Each sequence owns an int32 block-table row mapping its
logical KV blocks to pool pages; both the tables ``(B, M)`` and the
inclusive context positions ``(B,)`` ride in by scalar prefetch.

The work follows the live pages, not the table:
  * the pools stay in HBM (``memory_space=pl.ANY``), viewed as
    ``(num_pages, block_size * Hkv, d)``: with Hkv a multiple of the TPU's
    8-row tile (qwen3's 8 kv-heads) these are the pool's own bytes, so the
    view costs no copy, where ``(num_pages, block_size, Hkv * d)`` would
    relay out the whole pool every call; one page across all kv-heads is
    one contiguous DMA;
  * the grid is the member axis alone (one step per ensemble member); the
    body walks the live sequences and, for each, its pages from the first
    one in its window to the one holding its current position, in chunks
    of ``pages_per_step`` pages fetched with double-buffered async copies
    (the next chunk, of this sequence or the next live one, is in flight
    while the current one is scored);
  * a sequence with a negative context (done or free) moves no page and
    gets a zero output;
  * all kv-heads of a chunk are scored in one MXU product against every
    query head, and a mask keeps each query head to its own kv-head's
    rows (GQA), so a chunk needs no relayout in VMEM;
  * operands stay in the pool's dtype; scores, the running max and sum,
    the softmax and the accumulator are f32.

``paged_attention`` is written for one member; under ``jax.vmap`` over
members (queries and pools batched, tables and contexts shared, as the
serving engine calls it) the members become the kernel's grid axis.

Validated in interpret mode on CPU against ``ref.paged_attention``;
compiled for TPU v5e in ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF

# VMEM for the four chunk buffers (K and V, two of each)
KV_VMEM_BYTES = 2 << 20


def pages_per_step(block_size: int, row_bytes: int, num_blocks: int) -> int:
    """Pages fetched per chunk: as many as the four chunk buffers fit in
    ``KV_VMEM_BYTES``, at most a table row's worth."""
    page_bytes = block_size * row_bytes
    return max(1, min(num_blocks, KV_VMEM_BYTES // (4 * page_bytes)))


def _paged_kernel(
    tab_ref, ctx_ref, q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
    *, scale, window, softcap, bs, hkv, group, pps, num_seqs, num_blocks,
):
    member = pl.program_id(0)
    rows = pps * bs * hkv  # K/V rows of one chunk: (position, kv-head)

    def pages(b):
        """[lo, hi): the pages of sequence b's attended positions."""
        ctx = ctx_ref[b]
        lo = 0 if window is None else jnp.maximum(ctx - window + 1, 0) // bs
        hi = jnp.where(ctx < 0, lo, jnp.minimum(ctx // bs + 1, num_blocks))
        return lo, hi

    def next_live(b):
        """The first sequence from b on with a page to read, or num_seqs."""
        def empty(b):
            lo, hi = pages(jnp.minimum(b, num_seqs - 1))
            return (b < num_seqs) & (hi <= lo)
        return jax.lax.while_loop(empty, lambda b: b + 1, b)

    def chunk_copies(b, c, slot):
        lo, hi = pages(b)
        first = lo + c * pps
        for i in range(pps):
            page = first + i
            dst = pl.ds(i * bs * hkv, bs * hkv)
            yield page < hi, [
                pltpu.make_async_copy(hbm.at[member, tab_ref[b, jnp.minimum(page, num_blocks - 1)]],
                                      buf.at[slot, dst], sems.at[kv, slot])
                for kv, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))
            ]

    def start(b, c, slot):
        for live, copies in chunk_copies(b, c, slot):
            @pl.when(live)
            def _():
                for cp in copies:
                    cp.start()

    def wait(b, c, slot):
        for live, copies in chunk_copies(b, c, slot):
            @pl.when(live)
            def _():
                for cp in copies:
                    cp.wait()

    # query row r is kv-head r // group; chunk row j is position j // hkv
    # of the chunk and kv-head j % hkv
    shape = (hkv * group, rows)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    own_head = col % hkv == jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
    col_pos = col // hkv

    def attend(b, first_page, slot):
        ctx = ctx_ref[b]
        q = q_ref[b].astype(k_buf.dtype)  # (Hkv * G, d)
        k = k_buf[slot]  # (rows, d)
        v = v_buf[slot]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = first_page * bs + col_pos
        mask = own_head & (kpos <= ctx)
        if window is not None:
            mask &= (ctx - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # rows past a sequence's last page hold whatever an earlier chunk left:
    # masked, but they must be finite (0 * NaN is NaN)
    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)
    o_ref[...] = jnp.zeros_like(o_ref)

    b0 = next_live(0)

    @pl.when(b0 < num_seqs)
    def _():
        start(b0, 0, 0)

    def seq_body(carry):
        b, slot = carry
        lo, hi = pages(b)
        n = (hi - lo + pps - 1) // pps
        nxt = next_live(b + 1)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def chunk(c, slot):
            last = c + 1 == n
            nb = jnp.where(last, nxt, b)

            @pl.when(nb < num_seqs)
            def _():
                start(nb, jnp.where(last, 0, c + 1), 1 - slot)

            wait(b, c, slot)
            attend(b, lo + c * pps, slot)
            return 1 - slot

        slot = jax.lax.fori_loop(0, n, chunk, slot)
        o_ref[b] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)
        return nxt, slot

    jax.lax.while_loop(lambda carry: carry[0] < num_seqs, seq_body, (b0, 0))


def _paged_call(q, k_pages, v_pages, block_tables, context_lens,
                *, scale, window, softcap, interpret):
    """q (K, B, Hkv, G, d), pools (K, P, bs, Hkv, d) -> (K, B, Hkv, G, d)."""
    K, B, Hkv, G, d = q.shape
    _, P, bs, _, _ = k_pages.shape
    M = block_tables.shape[1]
    pps = pages_per_step(bs, Hkv * d * k_pages.dtype.itemsize, M)
    rows = pps * bs * Hkv
    kernel = functools.partial(
        _paged_kernel, scale=scale, window=window, softcap=softcap, bs=bs, hkv=Hkv,
        group=G, pps=pps, num_seqs=B, num_blocks=M,
    )
    qspec = pl.BlockSpec((None, B, Hkv * G, d), lambda m, tab, ctx: (m, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K,),
        in_specs=[qspec, pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=qspec,
        scratch_shapes=[
            pltpu.VMEM((2, rows, d), k_pages.dtype),
            pltpu.VMEM((2, rows, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((Hkv * G, 128), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((Hkv * G, 128), jnp.float32),  # l
            pltpu.VMEM((Hkv * G, d), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, B, Hkv * G, d), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(
        block_tables, context_lens, q.reshape(K, B, Hkv * G, d),
        k_pages.reshape(K, P, bs * Hkv, d), v_pages.reshape(K, P, bs * Hkv, d),
    )
    return out.reshape(K, B, Hkv, G, d)


def _member_kernel(**static):
    """``_paged_call`` as a function with a leading member axis whose vmap
    folds the mapped axis into that member axis, so a vmapped call is still
    one kernel with the members on its grid."""
    call = jax.custom_batching.custom_vmap(functools.partial(_paged_call, **static))

    @call.def_vmap
    def _vmap(axis_size, in_batched, q, k_pages, v_pages, block_tables, context_lens):
        args = (q, k_pages, v_pages, block_tables, context_lens)
        full = [x if bat else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, bat in zip(args, in_batched)]
        if any(in_batched[3:]):  # tables or contexts differ per mapped index
            return jax.lax.map(lambda a: call(*a), tuple(full)), True
        q, k_pages, v_pages = (x.reshape((-1,) + x.shape[2:]) for x in full[:3])
        out = call(q, k_pages, v_pages, block_tables, context_lens)
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return call


def paged_attention(
    q, k_pages, v_pages, block_tables, context_lens,
    *, scale=None, window=None, softcap=None, interpret: bool = True,
):
    """Single-token decode over a paged KV pool.

    q: (B, Hkv, G, d) current-position queries; k_pages/v_pages:
    (num_pages, block_size, Hkv, d); block_tables: (B, M) int32 page ids;
    context_lens: (B,) int32 INCLUSIVE current position (the token being
    decoded sits at kpos == context_lens[b], already written to its page);
    a negative position marks a sequence with nothing to attend, whose
    output is zero.  Returns (B, Hkv, G, d).
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    call = _member_kernel(scale=scale, window=window, softcap=softcap, interpret=interpret)
    return call(q[None], k_pages[None], v_pages[None], block_tables, context_lens)[0]
