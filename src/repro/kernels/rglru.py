"""RG-LRU linear-recurrence Pallas kernel (recurrentgemma hot loop).

    h_t = a_t * h_{t-1} + x_t          (elementwise over channels)

TPU-native chunked scan: grid (B, R/rblk, S/sblk) with the sequence axis
innermost ("arbitrary"); each block computes its local prefix scan fully
vectorized (superposition: h = local_scan(x) + cumprod(a) * h_carry) and the
carry crosses blocks through VMEM scratch.  HBM traffic is exactly one read
of (a, x) and one write of h — XLA's associative_scan does log(S) passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_block(a, x):
    """Vectorized within-block scan: returns (h_local, cumprod_a).
    a, x: (sblk, rblk) f32; h assumes zero carry.  Hillis-Steele doubling
    over sublane rolls: log2(sblk) steps, each one combine of the row with
    the row ``k`` above it, (a1, h1) . (a2, h2) = (a1*a2, a2*h1 + h2)."""
    sblk = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    A, h = a, x
    k = 1
    while k < sblk:
        has_prev = row >= k
        h = jnp.where(has_prev, A * pltpu.roll(h, k, 0) + h, h)
        A = jnp.where(has_prev, A * pltpu.roll(A, k, 0), A)
        k *= 2
    return h, A


def _rglru_kernel(a_ref, x_ref, h0_ref, o_ref, carry, *, num_sblocks):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        carry[...] = h0_ref[0].astype(jnp.float32)  # (1, rblk)

    a = a_ref[0].astype(jnp.float32)  # (sblk, rblk)
    x = x_ref[0].astype(jnp.float32)
    h_local, A = _scan_block(a, x)
    h = h_local + A * carry[...]  # (sblk, rblk) + (sblk,rblk)*(1,rblk)
    o_ref[0] = h.astype(o_ref.dtype)
    carry[...] = h[-1:, :]


def rglru_scan(a, x, h0=None, *, block_r: int = 128, block_s: int = 256, interpret: bool = True):
    """a, x: (B, S, R); h0: (B, R) or None. Returns h: (B, S, R)."""
    B, S, R = a.shape
    rblk = min(block_r, R)
    sblk = min(block_s, S)
    assert R % rblk == 0 and S % sblk == 0, (R, S, rblk, sblk)
    if h0 is None:
        h0 = jnp.zeros((B, R), jnp.float32)
    h0 = h0.reshape(B, 1, R)  # (1, 1, rblk) blocks: a whole-dim sublane axis
    grid = (B, R // rblk, S // sblk)
    kernel = functools.partial(_rglru_kernel, num_sblocks=S // sblk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, sblk, rblk), lambda b, r, t: (b, t, r)),
            pl.BlockSpec((1, sblk, rblk), lambda b, r, t: (b, t, r)),
            pl.BlockSpec((1, 1, rblk), lambda b, r, t: (b, 0, r)),
        ],
        out_specs=pl.BlockSpec((1, sblk, rblk), lambda b, r, t: (b, t, r)),
        out_shape=jax.ShapeDtypeStruct((B, S, R), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, rblk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, x, h0)
