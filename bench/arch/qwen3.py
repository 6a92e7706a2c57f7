"""The qwen3 decoder as the benchmark sees it: the program's ModelConfig
for a configuration file, the leaf layout and init scales of its weights,
the terms of its FLOP count and its KV bytes per token.

Layers are stacked on a leading axis; the vocabulary head is tied to the
embedding.  The FLOP terms follow the analytic model in the program's
roofline module (projections, attention over the attended context, gated
MLP, tied vocabulary head), copied here.  ``cfg`` is a configuration
file's dict (Hugging Face key names).
"""
from __future__ import annotations

import math


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    import jax.numpy as jnp

    from harness import CellError
    from repro import configs

    if not cfg["tie_word_embeddings"]:
        raise CellError("only tied-embedding qwen3 configurations are wired")
    return configs.get_config("qwen3-0.6b").replace(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
    )


def shapes(cfg: dict) -> dict:
    """Nested dict of leaf shapes, the program's checkpoint layout."""
    D, Hq, Hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    F, L, V = cfg["intermediate_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    layer = {
        "ln1": (L, D),
        "attn": {"wq": (L, D, Hq, dh), "wk": (L, D, Hkv, dh), "wv": (L, D, Hkv, dh),
                 "wo": (L, Hq, dh, D), "q_norm": (L, dh), "k_norm": (L, dh)},
        "ln2": (L, D),
        "mlp": {"w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)},
    }
    return {"embed": {"table": (V, D)}, "layers": {"0": layer}, "final_norm": (D,)}


NORM_GAINS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")


def std(path: tuple, cfg: dict) -> float:
    """Init scale of a leaf that is not a norm gain."""
    name = path[-1]
    if name == "table":
        return 0.02
    if name == "wo":
        return 1.0 / math.sqrt(cfg["num_attention_heads"] * cfg["head_dim"])
    if name == "w_down":
        return 1.0 / math.sqrt(cfg["intermediate_size"])
    return 1.0 / math.sqrt(cfg["hidden_size"])


def matmul_params(cfg: dict) -> int:
    """Weights that take part in a matmul per token: every layer's
    projections and MLP, plus the vocabulary head (tied or not, it is one
    D x V product per token; the embedding lookup is a gather)."""
    D, Hq, Hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    F, L, V = cfg["intermediate_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    per_layer = D * dh * (Hq + 2 * Hkv) + Hq * dh * D + 3 * D * F
    return L * per_layer + D * V


def attention_flops(cfg: dict, context: float) -> float:
    """QK^T and AV for one query token attending ``context`` positions,
    over all layers."""
    return 4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"] * context


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of one position's bfloat16 K and V rows over all layers, for
    one member."""
    return cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
