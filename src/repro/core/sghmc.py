"""Stochastic Gradient Hamiltonian Monte Carlo — paper Eq. (4).

    theta_{t+1} = theta_t + eps * M^{-1} p_t
    p_{t+1}     = p_t - eps * grad Ũ(theta_t) - eps * V M^{-1} p_t
                      + N(0, 2 eps V)            [noise_convention="eq4"]

V plays the double role of friction and injected-noise scale (the paper
follows Ma et al.'s complete-recipe form where D = diag([0, V])).  ``mass``
is the diagonal of M (scalar or pytree).  ``temperature`` scales the noise
covariance (1.0 = faithful sampler, 0.0 = deterministic momentum dynamics —
useful for tests and cold-posterior ablations).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .schedules import as_schedule
from .tree_util import global_norm, tree_random_normal
from .types import Sampler


class SGHMCState(NamedTuple):
    momentum: any
    step: jnp.ndarray


def _noise_scale(eps, friction, extra, convention: str):
    """Std-dev of injected noise. eq4: N(0, 2 eps V); eq6: N(0, 2 eps^2 (V+C))."""
    v = friction + extra
    if convention == "eq4":
        return jnp.sqrt(2.0 * eps * v)
    elif convention == "eq6":
        return eps * jnp.sqrt(2.0 * v)
    raise ValueError(f"unknown noise convention {convention!r}")


def sghmc(
    step_size,
    friction: float = 1.0,
    mass: float = 1.0,
    temperature: float = 1.0,
    noise_convention: str = "eq4",
    grad_noise_estimate: float = 0.0,
    state_dtype=jnp.float32,
) -> Sampler:
    """Plain SGHMC (single chain, or K independent chains if params carry a
    leading chain axis — there is no cross-leaf or cross-chain interaction).

    ``grad_noise_estimate`` is the B̂ term of Chen et al. (2014): injected
    noise becomes 2 eps (V - B̂) while friction stays V.
    ``state_dtype``: momentum storage dtype (bf16 at 100B+ scale; arithmetic
    is always f32 with cast-on-store).
    """
    schedule = as_schedule(step_size)
    minv = 1.0 / mass

    def init(params):
        return SGHMCState(
            momentum=jax.tree.map(lambda p: jnp.zeros_like(p, state_dtype), params),
            step=jnp.zeros((), jnp.int32),
        )

    @jax.named_scope("sampler.update")
    def update(grads, state, params=None, rng=None):
        del params
        eps = schedule(state.step)
        # position update uses the *current* momentum (Eq. 4 line 1)
        updates = jax.tree.map(lambda p: eps * minv * p.astype(jnp.float32), state.momentum)
        sigma = temperature**0.5 * _noise_scale(
            eps, friction - grad_noise_estimate, 0.0, noise_convention
        )
        noise = tree_random_normal(rng, state.momentum, jnp.float32)

        def mom_step(p, g, n):
            # decay form (1 - eps V M^-1) p: the association the fused
            # Pallas kernel uses, so the coupled sampler's unfused path
            # stays bit-identical at alpha=0
            p32 = p.astype(jnp.float32)
            out = (1.0 - eps * friction * minv) * p32 - eps * g.astype(jnp.float32) + sigma * n
            return out.astype(state_dtype)

        new_mom = jax.tree.map(mom_step, state.momentum, grads, noise)
        return updates, SGHMCState(momentum=new_mom, step=state.step + 1)

    def stats(state, params):
        del params
        return {"step": state.step, "momentum_norm": global_norm(state.momentum)}

    return Sampler(init, update, stats=stats)
