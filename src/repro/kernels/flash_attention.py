"""Blocked (flash) attention Pallas kernel — the model-side FLOP hot spot.

TPU-native design:
  * grid (B, Hq, Sq/bq, Sk/bk); the k dimension is the innermost
    ("arbitrary") axis with online-softmax state carried in VMEM scratch,
  * blocks sized to the MXU (bq x d and bk x d tiles, d a multiple of 128
    via padding in ops.py),
  * GQA folded into the index map (k/v blocks fetched once per kv-head),
  * sliding-window and causal masking SKIP whole k-blocks via pl.when —
    gemma/danube locality becomes block sparsity, not masked-out FLOPs,
  * optional logit softcap (gemma2/grok) fused into the score tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, window, softcap, bq, bk, num_kblocks,
):
    i = pl.program_id(2)  # query block
    j = pl.program_id(3)  # key block (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * bq
    k_start = j * bk
    relevant = jnp.bool_(True)
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window is not None:
        relevant &= k_start + bk - 1 >= q_start - window + 1

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == num_kblocks - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q, k, v, *, causal: bool = True, window=None, softcap=None, scale=None,
    block_q: int = 128, block_k: int = 128, interpret: bool = True,
):
    """q: (B, Hq, S, d); k, v: (B, Hkv, S, d) -> (B, Hq, S, d)."""
    B, Hq, S, d = q.shape
    Hkv = k.shape[1]
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    bq, bk = min(block_q, S), min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    grid = (B, Hq, S // bq, S // bk)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, num_kblocks=S // bk,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((bq, 128), jnp.float32),  # l
            pltpu.VMEM((bq, d), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
