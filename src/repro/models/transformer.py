"""Decoder-only LM engine covering 8/10 assigned archs (dense / MoE /
hybrid-recurrent / ssm / vlm backbones).

Layers are organized as a repeating block *pattern* (e.g. gemma3 = 5 local +
1 global) and scanned over pattern periods: params for pattern position i
are stacked with a leading (num_periods,) axis, so compile time is O(pattern)
instead of O(depth).  Remainder layers (depth % period) are applied unrolled.

Three entry points per model:
  train_nll(cfg, params, batch)            -> (sum_nll, token_count)
  prefill(cfg, params, batch)              -> (last_logits, cache)
  decode_step(cfg, params, cache, tokens)  -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as M
from . import recurrent as R
from .common import LayerKind, ModelConfig, ParamSpec


# ---------------------------------------------------------------------------
# Spec stacking (scan-over-periods)
# ---------------------------------------------------------------------------


def stack_specs(specs, n: int, axis_name=None):
    def f(s: ParamSpec) -> ParamSpec:
        return dataclasses.replace(s, shape=(n,) + s.shape, axes=(axis_name,) + s.axes)

    return jax.tree.map(f, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def _block_specs(cfg: ModelConfig, kind: LayerKind) -> dict:
    sp = {"ln1": L.norm_spec(cfg)}
    if kind.kind == "attn":
        sp["attn"] = L.attn_specs(cfg)
        sp["ln2"] = L.norm_spec(cfg)
        sp["mlp"] = M.moe_specs(cfg) if kind.moe else L.mlp_specs(cfg)
        if cfg.sandwich_norm:
            sp["post_ln1"] = L.norm_spec(cfg)
            sp["post_ln2"] = L.norm_spec(cfg)
    elif kind.kind == "rglru":
        sp["mix"] = R.rglru_specs(cfg)
        sp["ln2"] = L.norm_spec(cfg)
        sp["mlp"] = L.mlp_specs(cfg)
    elif kind.kind == "mlstm":
        sp["mix"] = R.mlstm_specs(cfg)
    elif kind.kind == "slstm":
        sp["mix"] = R.slstm_specs(cfg)
    else:
        raise ValueError(kind.kind)
    return sp


def _layout(cfg: ModelConfig):
    """(pattern P, num_periods, remainder kinds)."""
    P = len(cfg.pattern)
    n_periods = cfg.num_layers // P
    rem_kinds = cfg.layer_kinds[n_periods * P :]
    return P, n_periods, rem_kinds


def param_specs(cfg: ModelConfig) -> dict:
    P, n_periods, rem_kinds = _layout(cfg)
    specs = {
        "embed": L.embed_specs(cfg),
        "layers": {
            str(i): stack_specs(_block_specs(cfg, cfg.pattern[i]), n_periods)
            for i in range(P)
        },
        "final_norm": L.norm_spec(cfg),
    }
    if rem_kinds:
        specs["rem"] = {
            str(i): _block_specs(cfg, k) for i, k in enumerate(rem_kinds)
        }
    return specs


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _norm(cfg, x, w):
    return L.rms_norm(x, w, cfg.norm_eps, cfg.norm_scale_offset)


def apply_block(cfg: ModelConfig, kind: LayerKind, p, x, positions):
    if kind.kind == "attn":
        h = L.attention(cfg, p["attn"], _norm(cfg, x, p["ln1"]), positions, kind.window)
        if cfg.sandwich_norm:
            h = _norm(cfg, h, p["post_ln1"])
        x = x + h
        h_in = _norm(cfg, x, p["ln2"])
        h = M.moe_ffn(cfg, p["mlp"], h_in) if kind.moe else L.mlp(cfg, p["mlp"], h_in)
        if cfg.sandwich_norm:
            h = _norm(cfg, h, p["post_ln2"])
        return x + h
    if kind.kind == "rglru":
        x = x + R.rglru_block(cfg, p["mix"], _norm(cfg, x, p["ln1"]))
        return x + L.mlp(cfg, p["mlp"], _norm(cfg, x, p["ln2"]))
    if kind.kind == "mlstm":
        return x + R.mlstm_block(cfg, p["mix"], _norm(cfg, x, p["ln1"]))
    if kind.kind == "slstm":
        return x + R.slstm_block(cfg, p["mix"], _norm(cfg, x, p["ln1"]))
    raise ValueError(kind.kind)


def decode_block(cfg: ModelConfig, kind: LayerKind, p, x, cache, t):
    if kind.kind == "attn":
        h, new_attn = L.decode_attention(
            cfg, p["attn"], _norm(cfg, x, p["ln1"]), cache["attn"], t, kind.window
        )
        if cfg.sandwich_norm:
            h = _norm(cfg, h, p["post_ln1"])
        x = x + h
        h_in = _norm(cfg, x, p["ln2"])
        h = M.moe_ffn(cfg, p["mlp"], h_in) if kind.moe else L.mlp(cfg, p["mlp"], h_in)
        if cfg.sandwich_norm:
            h = _norm(cfg, h, p["post_ln2"])
        return x + h, {"attn": new_attn}
    if kind.kind == "rglru":
        h, new_mix = R.rglru_decode(cfg, p["mix"], _norm(cfg, x, p["ln1"]), cache["mix"])
        x = x + h
        return x + L.mlp(cfg, p["mlp"], _norm(cfg, x, p["ln2"])), {"mix": new_mix}
    if kind.kind == "mlstm":
        h, new_mix = R.mlstm_decode(cfg, p["mix"], _norm(cfg, x, p["ln1"]), cache["mix"])
        return x + h, {"mix": new_mix}
    if kind.kind == "slstm":
        h, new_mix = R.slstm_decode(cfg, p["mix"], _norm(cfg, x, p["ln1"]), cache["mix"])
        return x + h, {"mix": new_mix}
    raise ValueError(kind.kind)


def _block_cache(cfg: ModelConfig, kind: LayerKind, batch: int, max_seq: int, dtype, abstract: bool):
    if kind.kind == "attn":
        fn = L.cache_specs if abstract else L.init_cache
        return {"attn": fn(cfg, batch, max_seq, kind.window, dtype)}
    fn = {
        "rglru": R.rglru_state_specs if abstract else R.rglru_init_state,
        "mlstm": R.mlstm_state_specs if abstract else R.mlstm_init_state,
        "slstm": R.slstm_state_specs if abstract else R.slstm_init_state,
    }[kind.kind]
    return {"mix": fn(cfg, batch, dtype)}


def _stack_cache(tree, n: int, abstract: bool):
    if abstract:
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree
        )
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), tree)


def make_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype, abstract: bool = False):
    P, n_periods, rem_kinds = _layout(cfg)
    cache = {
        "layers": {
            str(i): _stack_cache(
                _block_cache(cfg, cfg.pattern[i], batch, max_seq, dtype, abstract),
                n_periods,
                abstract,
            )
            for i in range(P)
        },
        "t": jax.ShapeDtypeStruct((), jnp.int32) if abstract else jnp.zeros((), jnp.int32),
    }
    if rem_kinds:
        cache["rem"] = {
            str(i): _block_cache(cfg, k, batch, max_seq, dtype, abstract)
            for i, k in enumerate(rem_kinds)
        }
    return cache


def _block_cache_axes(kind: LayerKind, stacked: bool):
    lead = (None,) if stacked else ()
    if kind.kind == "attn":
        kv = lead + ("batch", "kvseq", "kv_heads", None)
        return {"attn": {"k": kv, "v": kv}}
    if kind.kind == "rglru":
        return {
            "mix": {"h": lead + ("batch", "rnn"), "conv": lead + ("batch", None, "rnn")}
        }
    if kind.kind == "mlstm":
        return {
            "mix": {
                "C": lead + ("batch", "heads", None, None),
                "n": lead + ("batch", "heads", None),
                "m": lead + ("batch", "heads"),
                "conv": lead + ("batch", None, "mlp"),
            }
        }
    if kind.kind == "slstm":
        ax = lead + ("batch", "heads", None)
        return {"mix": {"h": ax, "c": ax, "n": ax, "m": ax}}
    raise ValueError(kind.kind)


def cache_axes(cfg: ModelConfig):
    """Logical-axis tree matching make_cache structure (for sharding)."""
    P, n_periods, rem_kinds = _layout(cfg)
    out = {
        "layers": {str(i): _block_cache_axes(cfg.pattern[i], True) for i in range(P)},
        "t": (),
    }
    if rem_kinds:
        out["rem"] = {str(i): _block_cache_axes(k, False) for i, k in enumerate(rem_kinds)}
    return out


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _positions(cfg: ModelConfig, batch, B, S):
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def _embed_inputs(cfg: ModelConfig, params, batch):
    """tokens (+ optional precomputed patch/frame embeddings prepended)."""
    x = L.embed(cfg, params["embed"], batch["tokens"])
    if "patch_embeds" in batch:
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
    return x


def backbone(cfg: ModelConfig, params, x, positions, remat: bool | None = None):
    P, n_periods, rem_kinds = _layout(cfg)
    if remat is None:
        remat = cfg.remat == "full"

    def period(x, pslice):
        for i in range(P):
            x = apply_block(cfg, cfg.pattern[i], pslice[str(i)], x, positions)
        return x, None

    body = jax.checkpoint(period, policy=jax.checkpoint_policies.nothing_saveable) if remat else period
    x, _ = jax.lax.scan(body, x, params["layers"])
    for i, kind in enumerate(rem_kinds):
        x = apply_block(cfg, kind, params["rem"][str(i)], x, positions)
    return _norm(cfg, x, params["final_norm"])


def train_nll(cfg: ModelConfig, params, batch):
    """batch: tokens (B,S), labels (B,S), optional mask/positions/patch_embeds.
    Returns (sum_nll, token_count)."""
    B = batch["tokens"].shape[0]
    x = _embed_inputs(cfg, params, batch)
    S = x.shape[1]
    positions = _positions(cfg, batch, B, S)
    x = backbone(cfg, params, x, positions)
    n_prefix = x.shape[1] - batch["labels"].shape[1]
    if n_prefix:
        x = x[:, n_prefix:]
    return L.chunked_xent(cfg, params["embed"], x, batch["labels"], batch.get("mask"))


def prefill(cfg: ModelConfig, params, batch, max_seq: int, cache_dtype=None):
    """Run the full prompt, building the decode cache; returns
    (last_token_logits (B,1,V), cache).  Implemented as backbone + cache
    construction via decode-compatible state extraction."""
    B = batch["tokens"].shape[0]
    x = _embed_inputs(cfg, params, batch)
    S = x.shape[1]
    positions = _positions(cfg, batch, B, S)
    cache = make_cache(cfg, B, max_seq, cache_dtype or cfg.compute_dtype)
    P, n_periods, rem_kinds = _layout(cfg)

    def period(carry, xs):
        x = carry
        pslice, cslice = xs
        new_c = {}
        for i in range(P):
            x, new_c[str(i)] = _prefill_block(
                cfg, cfg.pattern[i], pslice[str(i)], x, cslice[str(i)], positions, max_seq
            )
        return x, new_c

    x, new_layer_caches = jax.lax.scan(period, x, (params["layers"], cache["layers"]))
    out_cache = {"layers": new_layer_caches, "t": jnp.asarray(S, jnp.int32)}
    if rem_kinds:
        out_cache["rem"] = {}
        for i, kind in enumerate(rem_kinds):
            x, out_cache["rem"][str(i)] = _prefill_block(
                cfg, kind, params["rem"][str(i)], x, cache["rem"][str(i)], positions, max_seq
            )
    x = _norm(cfg, x, params["final_norm"])
    logits = L.final_logits(cfg, params["embed"], x[:, -1:])
    return logits, out_cache


def _prefill_block(cfg, kind, p, x, cache, positions, max_seq):
    """apply_block + fill this layer's cache from the full-sequence pass."""
    if kind.kind == "attn":
        # recompute k/v once more for cache write (cheap vs attention itself)
        xin = _norm(cfg, x, p["ln1"])
        _, k, v = L._qk(cfg, p["attn"], xin, positions)
        Lc = cache["attn"]["k"].shape[1]
        S = k.shape[1]
        if S >= Lc:  # window (or exactly-full) cache: keep last Lc entries
            new_cache = {
                "k": k[:, S - Lc :].astype(cache["attn"]["k"].dtype),
                "v": v[:, S - Lc :].astype(cache["attn"]["v"].dtype),
            }
            if kind.window and S > Lc:
                # ring-buffer alignment: slot j holds pos with pos % Lc == j
                shift = S % Lc
                new_cache = {
                    kk: jnp.roll(vv, shift, axis=1) for kk, vv in new_cache.items()
                }
        else:
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    cache["attn"]["k"], k.astype(cache["attn"]["k"].dtype), 0, axis=1
                ),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    cache["attn"]["v"], v.astype(cache["attn"]["v"].dtype), 0, axis=1
                ),
            }
        return apply_block(cfg, kind, p, x, positions), {"attn": new_cache}
    # recurrent kinds: run the parallel block for outputs, then one scan pass
    # to extract the final state cheaply where possible.
    if kind.kind == "rglru":
        xin = _norm(cfg, x, p["ln1"])
        out, state = _rglru_with_state(cfg, p["mix"], xin)
        x = x + out
        x = x + L.mlp(cfg, p["mlp"], _norm(cfg, x, p["ln2"]))
        return x, {"mix": state}
    if kind.kind in ("mlstm", "slstm"):
        xin = _norm(cfg, x, p["ln1"])
        if kind.kind == "mlstm":
            out, state = _mlstm_with_state(cfg, p["mix"], xin)
        else:
            out, state = _slstm_with_state(cfg, p["mix"], xin)
        return x + out, {"mix": state}
    raise ValueError(kind.kind)


def _rglru_with_state(cfg, p, x):
    out = R.rglru_block(cfg, p, x)
    # final state: rerun last conv inputs; h from scan end. To stay O(S) we
    # recompute the recurrence's final h via a short scan over the sequence.
    B, S, D = x.shape
    cd = cfg.compute_dtype
    u = x.astype(cd) @ p["w_x"].astype(cd)
    W = p["conv_w"].shape[0]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    conv_state = pad[:, S : S + W - 1, :]  # last W-1 raw inputs
    uc = sum(pad[:, i : i + S, :] * p["conv_w"][i].astype(cd) for i in range(W)) + p[
        "conv_b"
    ].astype(cd)
    a, x_in = R._rglru_gates(p, uc)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, x_in), axis=1)
    state = {"h": h[:, -1], "conv": conv_state.astype(x.dtype)}
    return out, state


def _mlstm_with_state(cfg, p, x):
    out = R.mlstm_block(cfg, p, x)
    # final (C, n, m) via a scan over tokens (state extraction only).
    B, S, D = x.shape
    state = R.mlstm_init_state(cfg, B, x.dtype)

    def step(st, i):
        _, st2 = R.mlstm_decode(cfg, p, jax.lax.dynamic_slice_in_dim(x, i, 1, 1), st)
        return st2, None

    state, _ = jax.lax.scan(step, state, jnp.arange(S))
    return out, state


def _slstm_with_state(cfg, p, x):
    B, S, D = x.shape
    state0 = R.slstm_init_state(cfg, B, x.dtype)

    def step(st, xt):
        new = R._slstm_cell(p, xt, st)
        return new, new["h"]

    state, hs = jax.lax.scan(step, state0, jnp.moveaxis(x.astype(jnp.float32), 1, 0))
    hs = jnp.moveaxis(hs, 0, 1).reshape(B, S, D)
    return R._slstm_out(cfg, p, hs), state


# ---------------------------------------------------------------------------
# Paged decode (block-paged KV pools; DESIGN.md §8)
# ---------------------------------------------------------------------------


def check_paged_support(cfg: ModelConfig) -> None:
    """Paged pools hold absolute-position pages, so every layer must be
    non-windowed attention (windowed dense caches are ring buffers whose
    slot->position map does not survive the page indirection) and RoPE must
    be single-stream."""
    for kind in cfg.layer_kinds:
        if kind.kind != "attn":
            raise ValueError(f"paged decode supports attn-only models, got {kind.kind!r}")
        if kind.window:
            raise ValueError("paged decode does not support sliding-window layers")
    if cfg.mrope_sections is not None:
        raise ValueError("paged decode does not support M-RoPE position streams")


def make_paged_pools(cfg: ModelConfig, num_pages: int, block_size: int, dtype,
                     abstract: bool = False):
    """Flat page pools mirroring the make_cache layer structure: leaves
    (n_periods, num_pages, bs, Hkv, dh) for pattern layers (page 0 is the
    reserved sink).  No "t" leaf — positions live in the engine's per-slot
    context lengths."""
    check_paged_support(cfg)
    P, n_periods, rem_kinds = _layout(cfg)
    fn = L.page_pool_specs if abstract else L.init_page_pool
    pools = {
        "layers": {
            str(i): _stack_cache(
                {"attn": fn(cfg, num_pages, block_size, dtype)}, n_periods, abstract
            )
            for i in range(P)
        }
    }
    if rem_kinds:
        pools["rem"] = {
            str(i): {"attn": fn(cfg, num_pages, block_size, dtype)}
            for i in range(len(rem_kinds))
        }
    return pools


def _scatter_pages(pool_leaf, cache_leaf, table_row, block_size, stacked):
    """Write one slot's dense prefill cache (.., 1, L, Hkv, dh) into its
    table row's pages.  L is ceil-padded to M*bs; overflow blocks land in
    whatever table_row maps them to — the sink for unallocated tails."""
    M = table_row.shape[0]
    c = cache_leaf[:, 0] if stacked else cache_leaf[0]  # (P?, L, Hkv, dh)
    seq_ax = 1 if stacked else 0
    pad = M * block_size - c.shape[seq_ax]
    if pad:
        widths = [(0, 0)] * c.ndim
        widths[seq_ax] = (0, pad)
        c = jnp.pad(c, widths)
    blocks = c.reshape(c.shape[:seq_ax] + (M, block_size) + c.shape[seq_ax + 1 :])
    if stacked:
        return pool_leaf.at[:, table_row].set(blocks.astype(pool_leaf.dtype))
    return pool_leaf.at[table_row].set(blocks.astype(pool_leaf.dtype))


def paged_prefill_write(cfg: ModelConfig, pools, slot_cache, table_row, block_size: int):
    """Scatter a freshly prefilled slot cache (from :func:`prefill` with
    batch=1) into the paged pools along ``table_row`` (M,) int32.  Shared
    prefix pages are rewritten with bit-identical content (KV at position p
    depends only on (token_p, p)), so refcounted sharing stays exact."""
    P, n_periods, rem_kinds = _layout(cfg)
    out = {"layers": {}}
    for i in range(P):
        out["layers"][str(i)] = {
            "attn": {
                kk: _scatter_pages(
                    pools["layers"][str(i)]["attn"][kk],
                    slot_cache["layers"][str(i)]["attn"][kk],
                    table_row, block_size, stacked=True,
                )
                for kk in ("k", "v")
            }
        }
    if rem_kinds:
        out["rem"] = {
            str(i): {
                "attn": {
                    kk: _scatter_pages(
                        pools["rem"][str(i)]["attn"][kk],
                        slot_cache["rem"][str(i)]["attn"][kk],
                        table_row, block_size, stacked=False,
                    )
                    for kk in ("k", "v")
                }
            }
            for i in range(len(rem_kinds))
        }
    return out


def _paged_decode_block(cfg, kind, p, x, pool, block_tables, context_lens, write_block, sharded):
    h, new_attn = L.paged_decode_attention(
        cfg, p["attn"], _norm(cfg, x, p["ln1"]), pool["attn"],
        block_tables, context_lens, write_block, sharded=sharded,
    )
    if cfg.sandwich_norm:
        h = _norm(cfg, h, p["post_ln1"])
    x = x + h
    h_in = _norm(cfg, x, p["ln2"])
    h = M.moe_ffn(cfg, p["mlp"], h_in) if kind.moe else L.mlp(cfg, p["mlp"], h_in)
    if cfg.sandwich_norm:
        h = _norm(cfg, h, p["post_ln2"])
    return x + h, {"attn": new_attn}


def paged_decode_step(cfg: ModelConfig, params, pools, tokens, block_tables,
                      context_lens, write_block, *, sharded: bool = False):
    """All-slots-jointly decode: tokens (S, 1), block_tables (S, M) int32,
    context_lens (S,) int32 current positions, write_block (S,) int32
    destination pages (0, the sink, for a done or free slot); ``sharded``:
    the pools are split across devices.  Returns (logits (S, 1, V), new
    pools).  The shared page pools preclude a slot vmap — the slot axis is
    the batch axis."""
    x = L.embed(cfg, params["embed"], tokens)
    P, n_periods, rem_kinds = _layout(cfg)

    def period(carry, xs):
        x = carry
        pslice, poolslice = xs
        new_p = {}
        for i in range(P):
            x, new_p[str(i)] = _paged_decode_block(
                cfg, cfg.pattern[i], pslice[str(i)], x, poolslice[str(i)],
                block_tables, context_lens, write_block, sharded,
            )
        return x, new_p

    x, new_layer_pools = jax.lax.scan(period, x, (params["layers"], pools["layers"]))
    new_pools = {"layers": new_layer_pools}
    if rem_kinds:
        new_pools["rem"] = {}
        for i, kind in enumerate(rem_kinds):
            x, new_pools["rem"][str(i)] = _paged_decode_block(
                cfg, kind, params["rem"][str(i)], x, pools["rem"][str(i)],
                block_tables, context_lens, write_block, sharded,
            )
    x = _norm(cfg, x, params["final_norm"])
    logits = L.final_logits(cfg, params["embed"], x)
    return logits, new_pools


def decode_step(cfg: ModelConfig, params, cache, tokens):
    """tokens: (B, 1) -> (logits (B,1,V), new cache). One new position."""
    t = cache["t"]
    x = L.embed(cfg, params["embed"], tokens)
    P, n_periods, rem_kinds = _layout(cfg)

    def period(carry, xs):
        x = carry
        pslice, cslice = xs
        new_c = {}
        for i in range(P):
            x, new_c[str(i)] = decode_block(cfg, cfg.pattern[i], pslice[str(i)], x, cslice[str(i)], t)
        return x, new_c

    x, new_layer_caches = jax.lax.scan(period, x, (params["layers"], cache["layers"]))
    new_cache = {"layers": new_layer_caches, "t": t + 1}
    if rem_kinds:
        new_cache["rem"] = {}
        for i, kind in enumerate(rem_kinds):
            x, new_cache["rem"][str(i)] = decode_block(
                cfg, kind, params["rem"][str(i)], x, cache["rem"][str(i)], t
            )
    x = _norm(cfg, x, params["final_norm"])
    logits = L.final_logits(cfg, params["embed"], x)
    return logits, new_cache
