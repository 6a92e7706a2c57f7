"""Plain reference of the SGHMC update, as the paper writes it (Eq. 4):

    theta' = theta + eps M^-1 p
    p'     = (1 - eps V M^-1) p - eps g + sigma n,  sigma = eps sqrt(2V)

with the momentum stored in the state dtype and updated in float32.  The
noise follows the deployment's key convention: the step's key is
``fold_in(run_key, step)``, and one normal per leaf is drawn from
``split(key, leaves)``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def tree_normal(key, tree):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [jax.random.normal(k, x.shape, jnp.float32) for k, x in zip(keys, leaves)])


def sghmc_step(dep: dict, theta, p, g, noise):
    """One SGHMC step of one chain; ``noise`` is its standard normal draw.
    Returns (theta', p')."""
    if dep["noise_convention"] != "eq6":
        raise ValueError("the reference states the eq6 noise convention only")
    eps, V, minv = dep["step_size"], dep["friction"], 1.0 / dep["mass"]
    sigma = eps * math.sqrt(2.0 * V)
    sd = p_dtype(p)
    new_p = jax.tree.map(
        lambda m, gg, n: ((1.0 - eps * V * minv) * m.astype(jnp.float32) - eps * gg
                          + sigma * n).astype(sd), p, g, noise)
    new_theta = jax.tree.map(lambda t, m: t + eps * minv * m.astype(jnp.float32), theta, p)
    return new_theta, new_p


def p_dtype(tree):
    return jax.tree.leaves(tree)[0].dtype
