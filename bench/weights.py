"""Seeded random weights for a qwen3-style decoder, in the layout the
system under test loads (layers stacked on a leading axis), made on the
device in one jitted call.

The layout is written out here from the configuration file, not read from
the program, so the reference and the program are handed the same arrays
by a third party.  ``check_layout`` compares it with what the program
expects before a run starts.
"""
from __future__ import annotations

import math


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


# norm gains are drawn as 1 + GAIN_STD * N(0, 1): a gain of exactly 1 would
# let a program that drops a gain, or applies it to the wrong tensor, serve
# the same logits as the reference
GAIN_STD = 0.1
NORM_GAINS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")


def _std(path: tuple, cfg: dict) -> float:
    """Init scale of a leaf (of its spread about 1, for a norm gain)."""
    name = path[-1]
    if name in NORM_GAINS:
        return GAIN_STD
    if name == "table":
        return 0.02
    if name == "wo":
        return 1.0 / math.sqrt(cfg["num_attention_heads"] * cfg["head_dim"])
    if name == "w_down":
        return 1.0 / math.sqrt(cfg["intermediate_size"])
    return 1.0 / math.sqrt(cfg["hidden_size"])


def _leaves(tree, prefix=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def _set(tree: dict, path: tuple, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make(cfg: dict, seed: int, copies: int, out_sharding=None):
    """``copies`` independent float32 weight sets stacked on a leading axis
    (ensemble members or chains), drawn from ``seed`` in one jitted call.
    ``out_sharding`` places the stack (e.g. one chain per chip)."""
    import jax
    import jax.numpy as jnp

    leaves = list(_leaves(shapes(cfg)))

    def one(key):
        out: dict = {}
        for i, (path, shape) in enumerate(leaves):
            val = _std(path, cfg) * jax.random.normal(jax.random.fold_in(key, i), shape,
                                                      jnp.float32)
            _set(out, path, 1.0 + val if path[-1] in NORM_GAINS else val)
        return out

    def build(key):
        keys = jax.vmap(lambda c: jax.random.fold_in(key, c))(jnp.arange(copies))
        return jax.vmap(one)(keys)

    # the seed goes in as data: one program serves every seed
    key = jax.random.fold_in(jax.random.key(0), jnp.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, jnp.uint32(seed >> 32))
    fn = jax.jit(build, out_shardings=out_sharding) if out_sharding is not None else jax.jit(build)
    return fn(key)


def check_layout(cfg: dict, program_specs) -> None:
    """Raise unless the program's parameter tree has exactly this layout."""
    import jax

    ours = {path: tuple(shape) for path, shape in _leaves(shapes(cfg))}
    flat, _ = jax.tree_util.tree_flatten_with_path(
        program_specs, is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))
    theirs = {tuple(getattr(k, "key", getattr(k, "name", str(k))) for k in path): tuple(leaf.shape)
              for path, leaf in flat}
    if ours != theirs:
        raise RuntimeError(f"weight layout differs from the program's: "
                           f"ours {sorted(set(ours.items()) - set(theirs.items()))[:4]}, "
                           f"program {sorted(set(theirs.items()) - set(ours.items()))[:4]}")
