"""Seeded random weights in the layout the system under test loads, made
on the device in one jitted call.

The layout and the init scales are the configuration's architecture
module's (``bench/arch/<model_type>.py``), written out there from the
configuration file, not read from the program, so the reference and the
program are handed the same arrays by a third party.  ``check_layout``
compares the layout with what the program expects before a run starts.
"""
from __future__ import annotations

import harness

# norm gains are drawn as 1 + GAIN_STD * N(0, 1): a gain of exactly 1 would
# let a program that drops a gain, or applies it to the wrong tensor, serve
# the same logits as the reference
GAIN_STD = 0.1


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

    arch = harness.arch(cfg)
    leaves = list(_leaves(arch.shapes(cfg)))

    def one(key):
        out: dict = {}
        for i, (path, shape) in enumerate(leaves):
            gain = path[-1] in arch.NORM_GAINS
            std = GAIN_STD if gain else arch.std(path, cfg)
            val = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            _set(out, path, 1.0 + val if gain else val)
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

    ours = {path: tuple(shape) for path, shape in _leaves(harness.arch(cfg).shapes(cfg))}
    flat, _ = jax.tree_util.tree_flatten_with_path(
        program_specs, is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))
    theirs = {tuple(getattr(k, "key", getattr(k, "name", str(k))) for k in path): tuple(leaf.shape)
              for path, leaf in flat}
    if ours != theirs:
        raise RuntimeError(f"weight layout differs from the program's: "
                           f"ours {sorted(set(ours.items()) - set(theirs.items()))[:4]}, "
                           f"program {sorted(set(theirs.items()) - set(ours.items()))[:4]}")
