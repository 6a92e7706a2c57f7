"""Plain reference of the SGHMC update, as the paper writes it (Eq. 4):

    theta' = theta + eps M^-1 p
    p'     = (1 - eps V M^-1) p - eps g + sigma n,  sigma = eps sqrt(2V)

and of its elastically coupled form (Eq. 6, ``ec_sghmc_step``), with the
sampler state stored in the state dtype and updated in float32.  The
noise follows the deployment's key convention: the step's key is
``fold_in(run_key, step)``, and one normal per leaf is drawn from
``split(key, leaves)``; the coupled sampler splits the step's key in two,
and chain i draws from ``fold_in(first, i)``, the centre from the second.
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


def ec_noise_scales(dep: dict, coupling: dict) -> tuple[float, float]:
    """(sigma_p, sigma_r) of ``ec_sghmc_step``."""
    eps, V, C = dep["step_size"], dep["friction"], coupling["center_friction"]
    sigma_p = eps * math.sqrt(2.0 * (V + (C if coupling["center_noise_in_p"] else 0.0)))
    return sigma_p, eps * math.sqrt(2.0 * C)


def ec_sghmc_step(dep: dict, coupling: dict, chain, centre, g, noise, sync: bool, chain_mean):
    """One step of elastically coupled SGHMC (Eq. 6) for one chain and the
    centre that every worker holds a copy of:

        theta' = theta + eps M^-1 p
        c'     = c     + eps M^-1 r
        p'     = (1 - eps V M^-1) p - eps g - eps alpha (theta - c~) + sigma_p n
        r'     = (1 - eps C M^-1) r - eps alpha (c - mean~)          + sigma_r n_r

    with sigma_p = eps sqrt(2 (V + C)) (the centre's noise in p, as printed;
    eps sqrt(2 V) without it) and sigma_r = eps sqrt(2 C).  c~ is the stale
    centre and mean~ the stale mean of the chains; on a step that ends with
    the exchange (``sync``: the s-th, 2s-th, ... step counted from 1) c~
    becomes c' and mean~ the chains' mean of theta', ``chain_mean`` of each
    leaf (the caller's mean over the chain axis).

    ``chain`` is (theta, p); ``centre`` is (c, r, c~, mean~), stored in the
    momentum's dtype and updated in float32; ``noise`` is (n, n_r), the
    chain's and the centre's standard normal draws; ``coupling`` holds
    ``alpha``, ``center_friction`` and ``center_noise_in_p``.  Returns
    ((theta', p'), (c', r', c~', mean~'))."""
    if dep["noise_convention"] != "eq6":
        raise ValueError("the reference states the eq6 noise convention only")
    eps, V, minv = dep["step_size"], dep["friction"], 1.0 / dep["mass"]
    alpha, C = coupling["alpha"], coupling["center_friction"]
    sigma_p, sigma_r = ec_noise_scales(dep, coupling)
    theta, p = chain
    c, r, c_stale, mean_stale = centre
    n_p, n_r = noise
    sd = p_dtype(p)
    f32 = lambda x: x.astype(jnp.float32)
    new_theta = jax.tree.map(lambda t, m: t + eps * minv * f32(m), theta, p)
    new_c = jax.tree.map(lambda x, m: (f32(x) + eps * minv * f32(m)).astype(sd), c, r)
    new_p = jax.tree.map(
        lambda m, gg, t, ct, n: ((1.0 - eps * V * minv) * f32(m) - eps * gg
                                 - eps * alpha * (t - f32(ct)) + sigma_p * n).astype(sd),
        p, g, theta, c_stale, n_p)
    new_r = jax.tree.map(
        lambda m, x, mt, n: ((1.0 - eps * C * minv) * f32(m) - eps * alpha * (f32(x) - f32(mt))
                             + sigma_r * n).astype(sd),
        r, c, mean_stale, n_r)
    if sync:
        c_stale = new_c
        mean_stale = jax.tree.map(lambda t: chain_mean(t).astype(sd), new_theta)
    return (new_theta, new_p), (new_c, new_r, c_stale, mean_stale)
