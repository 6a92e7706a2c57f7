"""jit'd dispatch wrappers for the Pallas kernels: shape guards, padding,
platform selection (interpret=True on CPU — the kernel body runs in Python
for validation; compiled on real TPU), and pytree-level entry points."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import bma_select as _bs
from . import flash_attention as _fa
from . import fused_ecsghmc as _fe
from . import paged_attention as _pa
from . import rglru as _rg


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --- fused EC-SGHMC ----------------------------------------------------------

_LANES = _fe.LANES
_ROWS = _fe.BLOCK_ROWS
_TILE = _LANES * _ROWS


def _pad_flat(x):
    n = x.size
    pad = (-n) % _TILE
    flat = jnp.pad(x.reshape(-1), (0, pad))
    return flat.reshape(-1, _LANES), n


def _noise_kwargs(key, shape):
    """The kernel's noise input for ``key``.  On TPU: an int32 seed word
    for the on-chip PRNG (the kernel mixes in the block index), so no noise
    tensor touches HBM.  Elsewhere: two uint32 bit tensors, so the pure-jnp
    reference sees identical randomness."""
    if _on_tpu():
        seed = jax.lax.bitcast_convert_type(jax.random.bits(key, (1,), jnp.uint32), jnp.int32)
        return {"seed": seed}
    k1, k2 = jax.random.split(key)
    return {
        "bits1": jax.random.bits(k1, shape, jnp.uint32),
        "bits2": jax.random.bits(k2, shape, jnp.uint32),
    }


@functools.partial(jax.jit, static_argnames=("stochastic_round",))
def fused_ec_update(
    theta, p, g, c_tilde, key,
    *, eps, friction, mass, alpha, sigma_p, stochastic_round=True,
):
    """Single-leaf fused Eq. 6 update. Returns (theta_new, p_new) in the
    input dtypes.  Noise bits: jax.random on CPU-validation path; on-chip
    PRNG seeded from ``key`` on TPU (zero HBM noise traffic)."""
    shape, dtype_t, dtype_p = theta.shape, theta.dtype, p.dtype
    t2, n = _pad_flat(theta)
    p2, _ = _pad_flat(p)
    g2, _ = _pad_flat(g.astype(jnp.float32))
    c2, _ = _pad_flat(jnp.broadcast_to(c_tilde, theta.shape))
    t_new, p_new = _fe.fused_ec_update_flat(
        t2, p2, g2, c2, **_noise_kwargs(key, t2.shape),
        eps=eps, friction=friction, mass=mass, alpha=alpha, sigma_p=sigma_p,
        stochastic_round=stochastic_round, interpret=not _on_tpu(),
    )
    t_new = t_new.reshape(-1)[:n].reshape(shape).astype(dtype_t)
    p_new = p_new.reshape(-1)[:n].reshape(shape).astype(dtype_p)
    return t_new, p_new


def fused_ec_update_tree(params, momentum, grads, center_stale, key, **hyper):
    """Pytree-level fused update (one kernel launch per leaf)."""
    leaves_t, treedef = jax.tree.flatten(params)
    leaves_p = treedef.flatten_up_to(momentum)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_c = treedef.flatten_up_to(center_stale)
    keys = jax.random.split(key, len(leaves_t))
    outs = [
        fused_ec_update(t, p, g, c, k, **hyper)
        for t, p, g, c, k in zip(leaves_t, leaves_p, leaves_g, leaves_c, keys)
    ]
    new_t = treedef.unflatten([o[0] for o in outs])
    new_p = treedef.unflatten([o[1] for o in outs])
    return new_t, new_p


@functools.partial(jax.jit, static_argnames=("stochastic_round",))
def fused_precond_ec_update(
    theta, p, g, c_tilde, minv, key,
    *, eps, friction, alpha, sigma_p, stochastic_round=True,
):
    """Single-leaf preconditioned fused Eq. 6 update: the scalar mass is
    replaced by an elementwise (frozen) diagonal M^-1 streamed as a tensor.
    Same noise/rounding conventions as ``fused_ec_update``."""
    shape, dtype_t, dtype_p = theta.shape, theta.dtype, p.dtype
    t2, n = _pad_flat(theta)
    p2, _ = _pad_flat(p)
    g2, _ = _pad_flat(g.astype(jnp.float32))
    c2, _ = _pad_flat(jnp.broadcast_to(c_tilde, theta.shape))
    m2, _ = _pad_flat(jnp.broadcast_to(minv, theta.shape).astype(jnp.float32))
    t_new, p_new = _fe.fused_precond_ec_update_flat(
        t2, p2, g2, c2, m2, **_noise_kwargs(key, t2.shape),
        eps=eps, friction=friction, alpha=alpha, sigma_p=sigma_p,
        stochastic_round=stochastic_round, interpret=not _on_tpu(),
    )
    t_new = t_new.reshape(-1)[:n].reshape(shape).astype(dtype_t)
    p_new = p_new.reshape(-1)[:n].reshape(shape).astype(dtype_p)
    return t_new, p_new


def fused_precond_ec_update_tree(params, momentum, grads, center_stale, minv, key, **hyper):
    """Pytree-level preconditioned fused update.  Key-split structure is
    identical to ``fused_ec_update_tree`` so the two dispatch paths see the
    same per-leaf noise streams for a given ``key``."""
    leaves_t, treedef = jax.tree.flatten(params)
    leaves_p = treedef.flatten_up_to(momentum)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_c = treedef.flatten_up_to(center_stale)
    leaves_m = treedef.flatten_up_to(minv)
    keys = jax.random.split(key, len(leaves_t))
    outs = [
        fused_precond_ec_update(t, p, g, c, m, k, **hyper)
        for t, p, g, c, m, k in zip(
            leaves_t, leaves_p, leaves_g, leaves_c, leaves_m, keys
        )
    ]
    new_t = treedef.unflatten([o[0] for o in outs])
    new_p = treedef.unflatten([o[1] for o in outs])
    return new_t, new_p


# --- flash attention ---------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale", "block_q", "block_k")
)
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None, scale=None,
                    block_q=128, block_k=128):
    """(B, Hq, S, d) x (B, Hkv, S, d)^2 -> (B, Hq, S, d). Pads d to 128."""
    d = q.shape[-1]
    pad_d = (-d) % 128
    if pad_d:
        padder = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        q, k, v = padder(q), padder(k), padder(v)
        # keep softmax scale defined by the ORIGINAL head dim
        scale = scale if scale is not None else 1.0 / np.sqrt(d)
    out = _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=not _on_tpu(),
    )
    return out[..., :d] if pad_d else out


# --- paged attention (decode) ------------------------------------------------


@functools.partial(jax.jit, static_argnames=("scale", "window", "softcap"))
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    *, scale=None, window=None, softcap=None):
    """q (B, Hkv, G, d) vs paged pool (num_pages, bs, Hkv, d) through
    (B, M) block tables -> (B, Hkv, G, d).  Pads d to 128 (softmax scale
    keeps the ORIGINAL head dim); context_lens is the inclusive current
    position."""
    d = q.shape[-1]
    pad_d = (-d) % 128
    if pad_d:
        scale = scale if scale is not None else 1.0 / np.sqrt(d)
        pad = lambda x: jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad_d),))
        q, k_pages, v_pages = pad(q), pad(k_pages), pad(v_pages)
    out = _pa.paged_attention(
        q, k_pages, v_pages,
        block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
        scale=scale, window=window, softcap=softcap, interpret=not _on_tpu(),
    )
    return out[..., :d] if pad_d else out


# --- fused BMA mixture + selection -------------------------------------------


@functools.partial(jax.jit, static_argnames=("mode", "temperature", "top_k"))
def fused_bma_select(logits, key, *, mode="probs", temperature=0.0, top_k=0):
    """(K, S, V) member logits -> (tokens (S,) int32, mixture logp (S, V)
    f32) in one memory pass.  The Gumbel draw happens OUT here with the
    caller's key so sampled tokens are bit-identical to
    ``jax.random.categorical(key, logp/T)`` on the unfused path."""
    K, S, V = logits.shape
    gumbel = jax.random.gumbel(key, (S, V), jnp.float32) if temperature > 0.0 else None
    return _bs.bma_select(
        logits, gumbel,
        mode=mode, temperature=temperature, top_k=top_k,
        interpret=not _on_tpu(),
    )


# --- RG-LRU scan -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_r", "block_s"))
def rglru_scan(a, x, h0=None, *, block_r=128, block_s=256):
    B, S, R = a.shape
    pad_r = (-R) % min(block_r, max(R, 1))
    if pad_r:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad_r)))
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_r)))
        if h0 is not None:
            h0 = jnp.pad(h0, ((0, 0), (0, pad_r)))
    out = _rg.rglru_scan(
        a, x, h0, block_r=block_r, block_s=block_s, interpret=not _on_tpu()
    )
    return out[..., :R] if pad_r else out
