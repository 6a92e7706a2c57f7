"""train_step builder: model + sampler -> one posterior-sampling step.

Params/grads carry a leading chain axis K (EC-SGHMC); the model forward is
vmapped over it.  Because chains are independent in the likelihood, the
gradient of the *summed* potential yields exactly the per-chain gradients.
The elastic-coupling collective lives inside ``sampler.update``.

Two layers, both consumed by ``repro.run.ChainExecutor``:

* ``make_grad_fn`` — ``(targets, batch) -> (grads, metrics)``: the piece
  an executor in sampler mode scans (gradients evaluated wherever
  ``Sampler.grad_targets`` points, e.g. stale worker snapshots) — pass it
  as ``ChainExecutor(sampler=..., grad_fn=make_grad_fn(...))``;
* ``make_train_step`` — the classic fused step
  ``(params, state, batch, rng) -> (params, state, metrics)`` built from
  the same grad_fn (and honoring ``grad_targets`` itself), for the
  executor's ``step_fn`` mode (what ``train/loop.py`` runs) and for tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import apply_updates, gaussian_prior
from repro.models import ModelDef
from repro.models.common import ModelConfig


def make_grad_fn(
    cfg: ModelConfig,
    model: ModelDef,
    n_data: int,
    weight_decay: float = 1e-5,
):
    """Gradient-of-potential closure: (targets, batch) -> (grads, metrics)."""
    prior = gaussian_prior(weight_decay)

    def potential(params, batch):
        def per_chain(p, b):
            sum_nll, count = model.train_nll(cfg, p, b)
            scale = jnp.float32(n_data) / jnp.maximum(count, 1.0)
            return scale * sum_nll + prior.energy(p), (sum_nll, count)

        u, aux = jax.vmap(per_chain)(params, batch)
        return jnp.sum(u), aux

    @jax.named_scope("sampler.grad")
    def grad_fn(targets, batch):
        (u, (sum_nll, count)), grads = jax.value_and_grad(potential, has_aux=True)(
            targets, batch
        )
        metrics = {
            "potential": u,
            "nll_per_token": jnp.sum(sum_nll) / jnp.maximum(jnp.sum(count), 1.0),
        }
        return grads, metrics

    return grad_fn


def make_train_step(
    cfg: ModelConfig,
    model: ModelDef,
    sampler,
    n_data: int,
    weight_decay: float = 1e-5,
):
    grad_fn = make_grad_fn(cfg, model, n_data, weight_decay)

    def train_step(params, state, batch, rng):
        targets = sampler.grad_targets(state, params) if sampler.grad_targets else params
        grads, metrics = grad_fn(targets, batch)
        updates, new_state = sampler.update(grads, state, params, rng)
        return apply_updates(params, updates), new_state, metrics

    return train_step
