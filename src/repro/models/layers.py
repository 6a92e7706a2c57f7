"""Shared neural-net layers: norms, RoPE/M-RoPE, GQA attention (full /
sliding-window / softcap / qk-norm) with KV-cache decode, gated MLPs,
embeddings, and seq-chunked cross-entropy.

All functions are pure; params are plain dicts built from ParamSpecs.
Compute happens in ``cfg.compute_dtype``; reductions in f32.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .common import LayerKind, ModelConfig, ParamSpec

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps: float, offset: float = 0.0):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return ((offset + w.astype(jnp.float32)) * x32 * inv).astype(dt)


def norm_spec(cfg: ModelConfig, dim=None) -> ParamSpec:
    init = "zeros" if cfg.norm_scale_offset else "ones"
    return ParamSpec((dim or cfg.d_model,), ("embed",), init=init, dtype=cfg.param_dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)  # (half,)


def apply_rope(x, positions, theta: float, mrope_sections=None):
    """x: (..., S, H, dh). positions: (B, S) int or (3, B, S) for M-RoPE."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (dh/2,)
    if mrope_sections is None:
        angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,dh/2)
    else:
        # M-RoPE: frequency dims split into sections, each driven by its own
        # position stream (temporal, height, width).
        assert positions.ndim == 3, "M-RoPE needs positions (3, B, S)"
        parts = []
        start = 0
        for i, sec in enumerate(mrope_sections):
            f = freqs[start : start + sec]
            parts.append(positions[i][..., None].astype(jnp.float32) * f)
            start += sec
        angles = jnp.concatenate(parts, axis=-1)  # (B,S,dh/2)
    cos = jnp.cos(angles)[..., None, :]  # (B,S,1,dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA; full / sliding-window; softcap; qk-norm; cache decode)
# ---------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig) -> dict:
    D, Hq, Hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pd = cfg.param_dtype
    specs = {
        "wq": ParamSpec((D, Hq, dh), ("embed", "heads", None), dtype=pd),
        "wk": ParamSpec((D, Hkv, dh), ("embed", "kv_heads", None), dtype=pd),
        "wv": ParamSpec((D, Hkv, dh), ("embed", "kv_heads", None), dtype=pd),
        "wo": ParamSpec((Hq, dh, D), ("heads", None, "embed"), dtype=pd),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((dh,), (None,), init="ones", dtype=pd)
        specs["k_norm"] = ParamSpec((dh,), (None,), init="ones", dtype=pd)
    return specs


def _softcap(scores, cap: Optional[float]):
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _qk(cfg: ModelConfig, p, x, positions):
    """Project + rope; returns q (B,S,Hkv,G,dh), k/v (B,S,Hkv,dh)."""
    cd = cfg.compute_dtype
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wv"].astype(cd))
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    q = q.reshape(B, S, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim)
    return q, k, v


def _scale(cfg: ModelConfig):
    return cfg.query_scale if cfg.query_scale is not None else 1.0 / math.sqrt(cfg.head_dim)


def attention(
    cfg: ModelConfig,
    p,
    x,
    positions,
    window: Optional[int],
    q_chunk: int = 1024,
    causal: bool = True,
):
    """Training/prefill attention, chunked over query blocks so the (S, S)
    score matrix is never materialized (peak: (B, q_chunk, Hq, S)).
    Causal by default; optionally sliding-window (q_pos - k_pos < window)."""
    cd = cfg.compute_dtype
    B, S, _ = x.shape
    q, k, v = _qk(cfg, p, x, positions)
    scale = _scale(cfg)
    # flash path assumes contiguous arange positions (block-index masking):
    # M-RoPE / custom-position batches stay on the chunked path.
    if cfg.use_flash_kernel and causal and cfg.mrope_sections is None and S % min(128, S) == 0:
        from repro.kernels.ops import flash_attention as _flash

        qf = jnp.moveaxis(q.reshape(B, S, cfg.num_heads, cfg.head_dim), 1, 2)
        out = _flash(
            qf, jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
            causal=True, window=window, softcap=cfg.attn_logit_softcap,
            scale=scale, block_q=min(128, S), block_k=min(128, S),
        )
        out = jnp.moveaxis(out, 1, 2)
        return jnp.einsum("bshk,hkd->bsd", out.astype(cd), p["wo"].astype(cd))
    q_chunk = min(q_chunk, S)
    while S % q_chunk:  # largest divisor of S (e.g. whisper's 1500 frames)
        q_chunk -= 1
    n_chunks = S // q_chunk
    kpos = positions if positions.ndim == 2 else positions[0]  # (B,S)

    def one_chunk(c):
        qs = jax.lax.dynamic_slice_in_dim(q, c * q_chunk, q_chunk, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(kpos, c * q_chunk, q_chunk, axis=1)
        s = jnp.einsum("bqhgk,bthk->bhgqt", qs.astype(cd), k.astype(cd)) * scale
        s = _softcap(s.astype(jnp.float32), cfg.attn_logit_softcap)
        mask = jnp.ones((B, q_chunk, S), bool)
        if causal:
            mask &= qp[:, :, None] >= kpos[:, None, :]  # (B,q,t)
        if window is not None:
            mask &= (qp[:, :, None] - kpos[:, None, :]) < window
        s = jnp.where(mask[:, None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(cd)
        return jnp.einsum("bhgqt,bthk->bqhgk", w, v.astype(cd))

    if n_chunks == 1:
        out = one_chunk(0)
    else:
        outs = jax.lax.map(one_chunk, jnp.arange(n_chunks))  # (n,B,q,Hkv,G,dh)
        out = jnp.moveaxis(outs, 0, 1).reshape(B, S, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim)
    out = out.reshape(B, S, cfg.num_heads, cfg.head_dim)
    return jnp.einsum("bshk,hkd->bsd", out.astype(cd), p["wo"].astype(cd))


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, window: Optional[int], dtype):
    """KV cache for one attention layer. Windowed layers use a ring buffer of
    length `window` — decisive for long_500k memory."""
    L = min(window, max_seq) if window else max_seq
    shape = (batch, L, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int, window: Optional[int], dtype):
    L = min(window, max_seq) if window else max_seq
    shape = (batch, L, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
    }


def decode_attention(cfg: ModelConfig, p, x, cache, t, window: Optional[int]):
    """Single-token decode. x: (B, 1, D); t: scalar current position.
    Returns (out (B,1,D), new_cache)."""
    cd = cfg.compute_dtype
    B = x.shape[0]
    pos = jnp.full((B, 1), t, jnp.int32)
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[None], (3, B, 1))
    q, k, v = _qk(cfg, p, x, pos)
    L = cache["k"].shape[1]
    slot = (t % L).astype(jnp.int32) if window else t.astype(jnp.int32)
    new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    # slot j holds absolute position: full cache -> j; ring -> t - ((t - j) mod L)
    j = jnp.arange(L)
    if window:
        kpos = t - ((t - j) % L)
    else:
        kpos = j
    valid = (kpos >= 0) & (kpos <= t)
    s = jnp.einsum("bqhgk,bthk->bhgqt", q.astype(cd), new_k.astype(cd)) * _scale(cfg)
    s = _softcap(s.astype(jnp.float32), cfg.attn_logit_softcap)
    s = jnp.where(valid[None, None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(cd)
    out = jnp.einsum("bhgqt,bthk->bqhgk", w, new_v.astype(cd))
    out = out.reshape(B, 1, cfg.num_heads, cfg.head_dim)
    out = jnp.einsum("bshk,hkd->bsd", out.astype(cd), p["wo"].astype(cd))
    return out, {"k": new_k, "v": new_v}


def paged_decode_attention(cfg: ModelConfig, p, x, pool, block_tables, context_lens, write_block,
                           *, sharded: bool = False):
    """Single-token decode against a block-paged KV pool (DESIGN.md §8).

    x: (S, 1, D) — every engine slot jointly (the pool is shared, so slots
    cannot be vmapped the way dense slot caches are).  pool: {"k","v"} of
    (num_pages, bs, Hkv, dh); block_tables (S, M) int32; context_lens (S,)
    int32 current positions; write_block (S,) int32 destination page for
    this step's k/v (page 0 is the sink — done/free slots write there and
    nothing ever reads it).  Returns (out (S, 1, D), new pool).

    On TPU, or where ``cfg.use_flash_kernel`` is set, attention is the
    paged Pallas kernel, which reads only the pages of live slots up to
    each slot's position; a slot writing to the sink is done or free, reads
    nothing and gets a zero output, which the engine discards.  Elsewhere,
    and where the pools are ``sharded`` across devices (a custom call
    GSPMD cannot partition), it is the gather path: every slot's whole
    table gathered, numerics mirroring :func:`decode_attention` exactly —
    einsums in ``compute_dtype``, softcap/softmax in f32, -1e30 masking —
    so paged vs dense equivalence holds at f32-roundoff tolerance.  The
    kernel's scores and softmax are f32 throughout."""
    cd = cfg.compute_dtype
    S = x.shape[0]
    pos = context_lens[:, None].astype(jnp.int32)  # (S, 1)
    q, k, v = _qk(cfg, p, x, pos)  # q (S,1,Hkv,G,dh), k/v (S,1,Hkv,dh)
    bs = pool["k"].shape[1]
    off = (context_lens % bs).astype(jnp.int32)
    new_k = pool["k"].at[write_block, off].set(k[:, 0].astype(pool["k"].dtype))
    new_v = pool["v"].at[write_block, off].set(v[:, 0].astype(pool["v"].dtype))
    window = None  # paged pools are non-windowed (guarded at pool creation)
    if (cfg.use_flash_kernel or jax.default_backend() == "tpu") and not sharded:
        from repro.kernels.ops import paged_attention as _paged

        live_ctx = jnp.where(write_block == 0, -1, context_lens)
        out = _paged(
            q[:, 0], new_k, new_v, block_tables, live_ctx,
            scale=_scale(cfg), window=window, softcap=cfg.attn_logit_softcap,
        )[:, None]  # (S, 1, Hkv, G, dh)
    else:
        M = block_tables.shape[1]
        kd = new_k[block_tables].reshape(S, M * bs, cfg.num_kv_heads, cfg.head_dim)
        vd = new_v[block_tables].reshape(S, M * bs, cfg.num_kv_heads, cfg.head_dim)
        kpos = jnp.arange(M * bs)[None, :]
        valid = kpos <= context_lens[:, None]
        s = jnp.einsum("bqhgk,bthk->bhgqt", q.astype(cd), kd.astype(cd)) * _scale(cfg)
        s = _softcap(s.astype(jnp.float32), cfg.attn_logit_softcap)
        s = jnp.where(valid[:, None, None, None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(cd)
        out = jnp.einsum("bhgqt,bthk->bqhgk", w, vd.astype(cd))
    out = out.reshape(S, 1, cfg.num_heads, cfg.head_dim)
    out = jnp.einsum("bshk,hkd->bsd", out.astype(cd), p["wo"].astype(cd))
    return out, {"k": new_k, "v": new_v}


def init_page_pool(cfg: ModelConfig, num_pages: int, block_size: int, dtype):
    """Paged KV pool for one attention layer: a flat page array shared by
    every sequence, indexed through per-sequence block tables."""
    shape = (num_pages, block_size, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def page_pool_specs(cfg: ModelConfig, num_pages: int, block_size: int, dtype):
    shape = (num_pages, block_size, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
    }


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def mlp_specs(cfg: ModelConfig, d_ff=None) -> dict:
    D, F, pd = cfg.d_model, d_ff or cfg.d_ff, cfg.param_dtype
    specs = {
        "w_up": ParamSpec((D, F), ("embed", "mlp"), dtype=pd),
        "w_down": ParamSpec((F, D), ("mlp", "embed"), dtype=pd),
    }
    if cfg.mlp_gated:
        specs["w_gate"] = ParamSpec((D, F), ("embed", "mlp"), dtype=pd)
    return specs


def mlp(cfg: ModelConfig, p, x):
    cd = cfg.compute_dtype
    act = _ACTS[cfg.act]
    if cfg.mlp_gated:
        h = act(x.astype(cd) @ p["w_gate"].astype(cd)) * (x.astype(cd) @ p["w_up"].astype(cd))
    else:
        h = act(x.astype(cd) @ p["w_up"].astype(cd))
    return h @ p["w_down"].astype(cd)


# ---------------------------------------------------------------------------
# Embeddings + chunked cross-entropy
# ---------------------------------------------------------------------------


def embed_specs(cfg: ModelConfig) -> dict:
    pd = cfg.param_dtype
    specs = {
        "table": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), scale=0.02, dtype=pd)
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec(
            (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), dtype=pd
        )
    return specs


def embed(cfg: ModelConfig, p, tokens):
    if cfg.embed_onehot:
        # TP-friendly lookup: contraction over the (sharded) vocab dim is a
        # local matmul + psum; the gather form all-gathers the whole table.
        oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cfg.compute_dtype)
        x = oh @ p["table"].astype(cfg.compute_dtype)
    else:
        x = jnp.take(p["table"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.embed_scale == "sqrt_d":
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.compute_dtype)
    return x


def _logits_chunk(cfg: ModelConfig, p, x):
    # f32 out of the dot itself: a compute-dtype result would be rounded
    # after an accumulation whose order the compiler picks per program, so
    # two programs sharing this model (the engine's fused and unfused
    # selection) would disagree by an ulp of bf16 and break token equality
    cd = cfg.compute_dtype
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x.astype(cd), p["table"].astype(cd),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x.astype(cd), p["unembed"].astype(cd),
                            preferred_element_type=jnp.float32)
    return _softcap(logits, cfg.final_logit_softcap)


def chunked_xent(cfg: ModelConfig, p, x, labels, mask=None):
    """sum_t NLL(labels_t), scanning over sequence chunks so the full
    (B, S, V) logits tensor never exists. Returns (sum_nll, token_count)."""
    B, S, D = x.shape
    C = min(cfg.xent_chunk, S)
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    if S % C:  # pad to a chunk multiple; padded positions masked out
        pad = C - S % C
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        S += pad
    n = S // C

    def body(acc, c):
        xs = jax.lax.dynamic_slice_in_dim(x, c * C, C, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, c * C, C, axis=1)
        ms = jax.lax.dynamic_slice_in_dim(mask, c * C, C, axis=1)
        logits = _logits_chunk(cfg, p, xs)  # (B,C,V) f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * ms
        return (acc[0] + jnp.sum(nll), acc[1] + jnp.sum(ms)), None

    (sum_nll, count), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), jnp.arange(n))
    return sum_nll, count


def final_logits(cfg: ModelConfig, p, x_last):
    """Logits for the last position only: x_last (B, 1, D) -> (B, 1, V)."""
    return _logits_chunk(cfg, p, x_last)
