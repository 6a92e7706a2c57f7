"""Elastically-Coupled SGHMC — the paper's contribution (Eq. 5/6).

K chains (theta^i, p^i) are coupled through a center variable c with its own
momentum r via the augmented Hamiltonian

    H(z) = sum_i [ U(theta^i) + p^iT M^-1 p^i ]
         + (1/K) sum_i (alpha/2) ||theta^i - c||^2  +  rT M^-1 r .

Discretized dynamics (Eq. 6), with the distributed-staleness model made
explicit (communication period ``s``):

    theta^i_{t+1} = theta^i_t + eps M^-1 p^i_t
    c_{t+1}       = c_t       + eps M^-1 r_t
    p^i_{t+1} = p^i_t - eps grad Ũ(theta^i_t) - eps V M^-1 p^i_t
                      - eps alpha (theta^i_t - c̃_t) + N(0, 2 eps^2 (V+C))
    r_{t+1}   = r_t   - eps C M^-1 r_t
                      - eps alpha (c_t - mean_thetã_t) + N(0, 2 eps^2 C)

where c̃ is the *stale* center snapshot each worker last received and
mean_thetã is the *stale* chain average the server last received — both
refreshed every ``s`` steps.  s=1 recovers the fully-synchronous coupled
system; alpha=0 recovers K independent SGHMC chains.

SPMD realization (see DESIGN.md §2): every leaf of params/grads carries a
leading chain axis of size K.  Chain states (momentum) carry the same axis;
center states do not.  When the chain axis is sharded over a mesh axis, the
``mean over axis 0`` executed inside the s-periodic ``lax.cond`` branch is
the ONLY cross-chain collective the compiled program contains — this is the
paper's communication pattern, verbatim.

The momentum update is dispatched through the fused Pallas kernel
(`repro.kernels.fused_ecsghmc`) when ``fused=True`` and shapes allow;
otherwise pure-jnp (identical math, unit-tested against each other).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .schedules import as_schedule
from .sghmc import _noise_scale
from .tree_util import (
    count_params,
    global_norm,
    tree_mean_axis0,
    tree_random_normal,
    tree_random_normal_per_chain,
)
from .types import Sampler


def p_step(p, g, theta, c_tilde, noise, *, eps, friction, minv, alpha, sigma_p,
           out_dtype=jnp.float32):
    """Eq. 6 momentum line, one leaf:  p' = (1 - eps V M^-1) p - eps g
    - eps alpha (theta - c̃) + sigma_p n.  The coupling force enters through
    the momentum — the paper's physics-respecting placement (vs. EAMSGD's
    position placement).

    Term grouping deliberately mirrors the fused Pallas kernel
    (`repro.kernels.fused_ecsghmc._kernel`) so that, given the same noise,
    the unfused and fused paths agree BIT-FOR-BIT in f32 — asserted by
    tests/test_fused_equivalence.py."""
    p32 = p.astype(jnp.float32)
    out = (
        (1.0 - eps * friction * minv) * p32
        - eps * g.astype(jnp.float32)
        - eps * alpha * (theta.astype(jnp.float32) - c_tilde.astype(jnp.float32))
        + sigma_p * noise
    )
    return out.astype(out_dtype)


class ECSGHMCState(NamedTuple):
    momentum: any  # p^i : (K, ...) per leaf
    center: any  # c : (...) per leaf
    center_momentum: any  # r : (...)
    center_stale: any  # c̃ : worker-side stale snapshot of c
    mean_theta_stale: any  # server-side stale mean_i theta^i
    step: jnp.ndarray


def ec_sghmc(
    step_size,
    alpha: float = 1.0,
    friction: float = 1.0,  # V
    center_friction: float = 1.0,  # C
    mass: float = 1.0,
    sync_every: int = 1,  # s
    temperature: float = 1.0,
    noise_convention: str = "eq6",
    center_noise_in_p: bool = True,
    compression=None,  # optional repro.distributed.compression codec for the sync
    fused: bool = False,
    state_dtype=jnp.float32,
    chain_axis: str | None = None,
    per_chain_noise: bool | None = None,
) -> Sampler:
    """``center_noise_in_p``: Eq. 6 as printed injects N(0, 2eps^2 (V+C))
    into p — the C part being the paper's *model* of center-staleness noise.
    When the center is genuinely stale (s > 1 in a real deployment) that
    noise already exists physically and injecting it again double-counts;
    set False to inject only the V part (total noise then matches 2 eps D
    when the staleness noise is real).  Faithful-to-paper default: True.

    ``chain_axis``: mesh axis name the leading chain axis is sharded over
    when the update runs inside ``shard_map`` (DESIGN.md §2/§7).  The
    s-periodic chain mean then reduces over that axis — still the program's
    only cross-chain collective: a pmean, or, with ``compression``, a
    single packed-int8 ``all_gather`` (~4x fewer wire bytes;
    ``distributed.compression.compressed_tree_mean``).  None (default)
    keeps the single-program SPMD emulation where the mean is a plain
    axis-0 reduction (``compression`` then quantizes the reduced mean —
    same noise model, no wire savings).

    ``per_chain_noise``: draw each chain's momentum noise from
    ``fold_in(step_key, global_chain_index)`` instead of one block draw
    per shard.  The stream then depends only on the global chain index, so
    any mesh layout of the same K chains — including the unsharded
    single-device program — sees bit-identical per-chain noise
    (the equivalence contract of DESIGN.md §7, gated by
    tests/test_sharding.py).  Defaults to True under ``chain_axis`` for
    the unfused path; the fused Pallas kernel generates block noise from
    counter bits and keeps the legacy per-shard stream."""
    schedule = as_schedule(step_size)
    minv = 1.0 / mass
    s = int(sync_every)
    if per_chain_noise is None:
        per_chain_noise = chain_axis is not None and not fused
    if per_chain_noise and fused:
        raise ValueError("per_chain_noise requires the unfused update "
                         "(the fused kernel draws block noise from counter bits)")

    def init(params):
        zeros = lambda p: jnp.zeros_like(p, state_dtype)
        center = tree_mean_axis0(jax.tree.map(lambda p: p.astype(state_dtype), params))
        # distinct buffers (aliasing would break XLA donation)
        copy = lambda t: jax.tree.map(jnp.copy, t)
        return ECSGHMCState(
            momentum=jax.tree.map(zeros, params),
            center=center,
            center_momentum=jax.tree.map(lambda c: jnp.zeros_like(c), center),
            center_stale=copy(center),
            mean_theta_stale=copy(center),
            step=jnp.zeros((), jnp.int32),
        )

    def update(grads, state, params, rng):
        eps = schedule(state.step)
        sigma_p = temperature**0.5 * _noise_scale(
            eps, friction, center_friction if center_noise_in_p else 0.0, noise_convention
        )
        sigma_r = temperature**0.5 * _noise_scale(eps, center_friction, 0.0, noise_convention)

        # -- position updates (use pre-update momenta; Eq. 6 lines 1-2) -----
        updates = jax.tree.map(lambda p: eps * minv * p.astype(jnp.float32), state.momentum)
        new_center = jax.tree.map(
            lambda c, r: (c.astype(jnp.float32) + eps * minv * r.astype(jnp.float32)).astype(
                state_dtype
            ),
            state.center,
            state.center_momentum,
        )

        # -- momentum updates ----------------------------------------------
        # shard_map: the caller passes a SHARD-INVARIANT key (DESIGN.md §2).
        # Per-chain noise must differ across shards — per_chain_noise folds
        # the GLOBAL chain index, the legacy block path folds the shard
        # index — while the center noise k_r stays identical everywhere, or
        # the replicated center state would silently random-walk apart.
        k_p, k_r = jax.random.split(rng)
        if chain_axis is not None and not per_chain_noise:
            k_p = jax.random.fold_in(k_p, jax.lax.axis_index(chain_axis))
        noise_r = tree_random_normal(k_r, state.center_momentum, jnp.float32)

        if fused:
            # one-pass Pallas kernel: theta'+p' fused, Box-Muller noise from
            # counter bits (on-chip PRNG on TPU), stochastic-rounded stores
            # for sub-f32 state dtypes. Same dynamics, same noise law.
            from repro.kernels.ops import fused_ec_update_tree

            new_theta_f, new_momentum = fused_ec_update_tree(
                params, state.momentum, grads, state.center_stale, k_p,
                eps=eps, friction=friction, mass=mass, alpha=alpha,
                sigma_p=sigma_p, stochastic_round=True,
            )
            del new_theta_f  # updates (above) already carry eps*M^-1*p
        else:
            if per_chain_noise:
                local_k = jax.tree.leaves(state.momentum)[0].shape[0]
                offset = (
                    jax.lax.axis_index(chain_axis) * local_k
                    if chain_axis is not None
                    else 0
                )
                noise_p = tree_random_normal_per_chain(
                    k_p, state.momentum, offset, jnp.float32
                )
            else:
                noise_p = tree_random_normal(k_p, state.momentum, jnp.float32)
            new_momentum = jax.tree.map(
                lambda p, g, th, ct, n: p_step(
                    p, g, th, ct, n, eps=eps, friction=friction, minv=minv,
                    alpha=alpha, sigma_p=sigma_p, out_dtype=state_dtype,
                ),
                state.momentum, grads, params, state.center_stale, noise_p,
            )

        def r_step(r, c, mth, n):
            r32 = r.astype(jnp.float32)
            out = (
                r32
                - eps * center_friction * minv * r32
                - eps * alpha * (c.astype(jnp.float32) - mth.astype(jnp.float32))
                + sigma_r * n
            )
            return out.astype(state_dtype)

        new_center_momentum = jax.tree.map(
            r_step, state.center_momentum, state.center, state.mean_theta_stale, noise_r
        )

        # -- s-periodic exchange (the ONLY cross-chain collective) ----------
        @jax.named_scope("sampler.exchange")
        def do_sync(operand):
            new_c, upd = operand
            # workers push theta^i (post-update), server replies with c.
            new_params = jax.tree.map(
                lambda th, u: th.astype(jnp.float32) + u, params, upd
            )
            if compression is not None and chain_axis is not None:
                # real wire compression: local mean -> packed int8 ->
                # ONE all_gather over the chain axis -> decode + average
                # (the program's only collective; ~4x fewer wire bytes)
                from repro.distributed.compression import compressed_tree_mean

                mean_theta = compressed_tree_mean(new_params, chain_axis)
            else:
                mean_theta = tree_mean_axis0(new_params, chain_axis)
                if compression is not None:
                    # single-program path: quantize the reduced mean —
                    # models the wire noise without moving fewer bytes
                    mean_theta = jax.tree.map(
                        lambda x: compression.decode(compression.encode(x)), mean_theta
                    )
            mean_theta = jax.tree.map(lambda x: x.astype(state_dtype), mean_theta)
            return new_c, mean_theta

        def no_sync(operand):
            del operand
            return state.center_stale, state.mean_theta_stale

        is_sync = (state.step + 1) % s == 0
        new_center_stale, new_mean_theta_stale = jax.lax.cond(
            is_sync, do_sync, no_sync, (new_center, updates)
        )

        new_state = ECSGHMCState(
            momentum=new_momentum,
            center=new_center,
            center_momentum=new_center_momentum,
            center_stale=new_center_stale,
            mean_theta_stale=new_mean_theta_stale,
            step=state.step + 1,
        )
        return updates, new_state

    def stats(state, params):
        """Jit-safe scalar diagnostics: the numbers repro.diagnostics and
        the drivers poll to watch coupling health without a host sync."""
        diff = jax.tree.map(
            lambda th, c: th.astype(jnp.float32) - c.astype(jnp.float32)[None],
            params,
            state.center,
        )
        n_elem = max(count_params(params), 1)
        rms = global_norm(diff) / jnp.sqrt(jnp.float32(n_elem))
        k = jax.tree.leaves(params)[0].shape[0]
        return {
            "step": state.step,
            "momentum_norm": global_norm(state.momentum),
            "center_momentum_norm": global_norm(state.center_momentum),
            "chain_center_rms": rms,
            # the Eq. 5 coupling energy (1/K) sum_i (alpha/2)||theta^i - c||^2
            "coupling_energy": 0.5 * alpha * rms * rms * (n_elem / k),
        }

    return Sampler(init, update, stats=stats)


def resample_chain_from_center(state: ECSGHMCState, alpha: float, rng, num_chains: int):
    """Elastic-K scaling / chain recovery: draw fresh chains from the
    stationary conditional  theta^i | c  ~  N(c, (alpha/K)^-1 I)  implied by
    the coupling term of Eq. 5, with zero momentum.  Returns (params, state)
    for the new chain count."""
    k = num_chains
    scale = (k / max(alpha, 1e-8)) ** 0.5

    def draw(c, key):
        return c[None] + scale * jax.random.normal(key, (k,) + c.shape, c.dtype)

    leaves, treedef = jax.tree.flatten(state.center)
    keys = jax.random.split(rng, len(leaves))
    params = jax.tree.unflatten(treedef, [draw(c, kk) for c, kk in zip(leaves, keys)])
    new_state = ECSGHMCState(
        momentum=jax.tree.map(lambda p: jnp.zeros_like(p), params),
        center=state.center,
        center_momentum=state.center_momentum,
        center_stale=state.center,
        mean_theta_stale=tree_mean_axis0(params),
        step=state.step,
    )
    return params, new_state
