"""Model substrate: configs + the ParamSpec machinery.

One source of truth per model: ``param_specs(cfg)`` returns a pytree of
:class:`ParamSpec`.  From it we derive
  * ``init_params``      — materialized params (smoke tests / real training)
  * ``abstract_params``  — ShapeDtypeStructs (dry-run: zero allocation)
  * ``param_axes``       — logical-axis names per dim (sharding rules)
so init, shapes, and sharding can never drift apart.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# ParamSpec machinery
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple  # logical axis name (str) or None per dim; len == len(shape)
    init: str = "normal"  # normal | zeros | ones | lru_lambda
    scale: float | str = "fan_in"  # stddev, or "fan_in" => 1/sqrt(fan_in dim)
    dtype: Any = jnp.float32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _materialize(spec: ParamSpec, key) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "lru_lambda":
        # RG-LRU Λ init: a = exp(-softplus(Λ)·c) uniform in [0.9, 0.999]
        u = jax.random.uniform(key, spec.shape, jnp.float32, 0.9, 0.999)
        # invert a = exp(-8·softplus(Λ)) -> Λ = softplus_inv(-log(a)/8)
        sp = -jnp.log(u) / 8.0
        lam = jnp.log(jnp.expm1(jnp.maximum(sp, 1e-8)))
        return lam.astype(spec.dtype)
    if spec.scale == "fan_in":
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
        std = 1.0 / math.sqrt(fan_in)
    else:
        std = float(spec.scale)
    return (std * jax.random.normal(key, spec.shape, jnp.float32)).astype(spec.dtype)


def init_params(specs, rng):
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(rng, len(leaves)) if leaves else []
    return jax.tree.unflatten(treedef, [_materialize(s, k) for s, k in zip(leaves, keys)])


def abstract_params(specs, dtype_override=None):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype_override or s.dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def param_axes(specs):
    return jax.tree.map(
        lambda s: s.axes, specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def cast_specs(specs, dtype):
    return jax.tree.map(
        lambda s: dataclasses.replace(s, dtype=dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LayerKind:
    kind: str  # attn | rglru | mlstm | slstm
    window: Optional[int] = None  # sliding-window size; None => full/global attn
    moe: bool = False


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | audio | hybrid | ssm | vlm
    vocab_size: int
    d_model: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    # repeating block pattern (cycled); remainder handled by truncation
    pattern: tuple = (LayerKind("attn"),)
    norm_eps: float = 1e-6
    norm_scale_offset: float = 0.0  # gemma: weight stored as (w - 1)
    sandwich_norm: bool = False  # gemma2/3: post-norms on both sublayers
    act: str = "silu"
    mlp_gated: bool = True  # False: plain 2-layer MLP (whisper)
    use_rope: bool = True  # False: absolute position embeddings (whisper)
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    qk_norm: bool = False
    rope_theta: float = 10000.0
    mrope_sections: Optional[tuple] = None  # qwen2-vl (t, h, w) freq split
    query_scale: Optional[float] = None  # None => 1/sqrt(head_dim)
    # MoE
    moe_num_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    # recurrent blocks
    rglru_conv_width: int = 4
    rnn_width: Optional[int] = None  # RG-LRU recurrence width (defaults d_model)
    # embeddings / head
    tie_embeddings: bool = True
    embed_scale: Optional[str] = None  # "sqrt_d" (gemma)
    embed_onehot: bool = False  # one_hot(tokens) @ table: TP-friendly lookup
    # (a gather from a vocab-sharded table forces an all-gather of the whole
    # table under GSPMD; the one-hot contraction partitions cleanly instead)
    # encoder-decoder (whisper): encoder layer count + source length
    enc_layers: int = 0
    enc_seq: int = 0
    # dtypes
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    # loss
    xent_chunk: int = 2048  # seq-chunked cross-entropy (never materialize B,S,V)
    # activation checkpointing: "full" (nothing saved, re-forward in bwd) or
    # "none" (save activations; +25% step speed when memory allows)
    remat: str = "full"
    # dispatch attention through the Pallas flash kernel (interpret-mode on
    # CPU; compiled on TPU). The §Perf lever that removes score
    # materialization; default off = paper-faithful XLA baseline.  Paged
    # decode takes its Pallas kernel on TPU either way; the flag sends it
    # there elsewhere too (interpret mode).
    use_flash_kernel: bool = False

    @property
    def layer_kinds(self) -> tuple:
        """Per-layer LayerKind, pattern cycled to num_layers."""
        p = self.pattern
        return tuple(p[i % len(p)] for i in range(self.num_layers))

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def num_params(cfg: ModelConfig) -> int:
    """Total parameter count derived from the spec tree (exact)."""
    from . import registry  # local import to avoid cycle

    specs = registry.get_model(cfg).param_specs(cfg)
    return sum(
        int(np.prod(s.shape))
        for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    )


def active_params(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: top_k of num_experts) — for 6·N_active·D."""
    from . import registry

    specs = registry.get_model(cfg).param_specs(cfg)
    total = 0
    for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec)):
        n = int(np.prod(s.shape))
        if "expert" in s.axes and cfg.moe_num_experts:
            n = n * cfg.moe_top_k // cfg.moe_num_experts
        total += n
    return total
