"""Satellite gate: the fused Pallas EC-SGHMC kernel (interpret mode,
stochastic rounding off, noise bits supplied) must match the pure-jnp
``p_step`` path of ``repro.core.ec_sghmc`` BIT-FOR-BIT in f32.

The two implementations share term grouping by construction (see the
``p_step`` docstring); both sides are jitted so XLA makes the same
contraction decisions.  Runs in a bare environment — no hypothesis.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ec_sghmc import p_step
from repro.kernels import ref
from repro.kernels.fused_ecsghmc import fused_ec_update_flat, fused_precond_ec_update_flat

SHAPE = (8, 1024)  # one kernel block


def _operands(seed):
    k = jax.random.PRNGKey(seed)
    kt, kp, kg, kc, k1, k2 = jax.random.split(k, 6)
    return (
        jax.random.normal(kt, SHAPE, jnp.float32),
        0.1 * jax.random.normal(kp, SHAPE, jnp.float32),
        jax.random.normal(kg, SHAPE, jnp.float32),
        jax.random.normal(kc, SHAPE, jnp.float32),
        jax.random.bits(k1, SHAPE, jnp.uint32),
        jax.random.bits(k2, SHAPE, jnp.uint32),
    )


@pytest.mark.parametrize("seed", [0, 42, 1234])
@pytest.mark.parametrize(
    "hyper",
    [
        dict(eps=1e-2, friction=1.0, mass=1.0, alpha=0.7, sigma_p=0.05),
        dict(eps=0.1, friction=1.5, mass=2.0, alpha=1.0, sigma_p=0.2),
        dict(eps=5e-3, friction=0.0, mass=1.0, alpha=0.0, sigma_p=0.0),
    ],
    ids=["paper", "heavy", "degenerate"],
)
def test_fused_matches_p_step_bitwise(seed, hyper):
    theta, p, g, c, bits1, bits2 = _operands(seed)

    @jax.jit
    def fused(theta, p, g, c, bits1, bits2):
        return fused_ec_update_flat(
            theta, p, g, c, bits1, bits2,
            stochastic_round=False, interpret=True, **hyper,
        )

    @jax.jit
    def unfused(theta, p, g, c, bits1, bits2):
        # identical noise law: Box-Muller from the same counter bits
        noise = ref.box_muller(bits1, bits2)
        p_new = p_step(
            p, g, theta, c, noise,
            eps=hyper["eps"], friction=hyper["friction"], minv=1.0 / hyper["mass"],
            alpha=hyper["alpha"], sigma_p=hyper["sigma_p"],
        )
        theta_new = theta + hyper["eps"] * (1.0 / hyper["mass"]) * p
        return theta_new, p_new

    t_f, p_f = fused(theta, p, g, c, bits1, bits2)
    t_u, p_u = unfused(theta, p, g, c, bits1, bits2)
    np.testing.assert_array_equal(np.asarray(t_f), np.asarray(t_u),
                                  err_msg="theta' not bit-identical")
    np.testing.assert_array_equal(np.asarray(p_f), np.asarray(p_u),
                                  err_msg="p' not bit-identical")


@pytest.mark.parametrize("seed", [0, 42])
@pytest.mark.parametrize(
    "hyper",
    [
        dict(eps=1e-2, friction=1.0, alpha=0.7, sigma_p=0.05),
        dict(eps=0.1, friction=1.5, alpha=1.0, sigma_p=0.2),
    ],
    ids=["paper", "heavy"],
)
def test_fused_precond_matches_p_step_bitwise(seed, hyper):
    """Preconditioned variant of the pin above: the M⁻¹-streaming kernel
    must match ``p_step`` with an *array* minv bit-for-bit, including the
    preconditioned drift theta' = theta + ε·M⁻¹·p."""
    theta, p, g, c, bits1, bits2 = _operands(seed)
    km = jax.random.PRNGKey(seed + 1000)
    # strictly positive, well away from 1.0 so the multiply is non-trivial
    minv = jnp.exp(0.5 * jax.random.normal(km, SHAPE, jnp.float32))

    @jax.jit
    def fused(theta, p, g, c, minv, bits1, bits2):
        return fused_precond_ec_update_flat(
            theta, p, g, c, minv, bits1, bits2,
            stochastic_round=False, interpret=True, **hyper,
        )

    @jax.jit
    def unfused(theta, p, g, c, minv, bits1, bits2):
        noise = ref.box_muller(bits1, bits2)
        p_new = p_step(
            p, g, theta, c, noise,
            eps=hyper["eps"], friction=hyper["friction"], minv=minv,
            alpha=hyper["alpha"], sigma_p=hyper["sigma_p"],
        )
        theta_new = theta + hyper["eps"] * minv * p
        return theta_new, p_new

    t_f, p_f = fused(theta, p, g, c, minv, bits1, bits2)
    t_u, p_u = unfused(theta, p, g, c, minv, bits1, bits2)
    np.testing.assert_array_equal(np.asarray(t_f), np.asarray(t_u),
                                  err_msg="theta' not bit-identical")
    np.testing.assert_array_equal(np.asarray(p_f), np.asarray(p_u),
                                  err_msg="p' not bit-identical")


def test_sampler_level_fused_equals_unfused_in_law():
    """End-to-end: one ec_sghmc step, fused vs unfused.  Different PRNG
    streams (counter bits vs jax.random.normal) forbid bitwise equality at
    the sampler level, but with temperature=0 the noise vanishes and the
    two dispatch paths must agree to f32 roundoff on identical dynamics."""
    from repro import core

    kw = dict(step_size=1e-2, alpha=1.0, temperature=0.0)
    params = jax.random.normal(jax.random.PRNGKey(5), (4, 128))
    grads = 1.3 * (params - 0.2)
    rng = jax.random.PRNGKey(7)

    outs = {}
    for fused in (False, True):
        sampler = core.ec_sghmc(fused=fused, **kw)
        st = sampler.init(params)
        # two steps so momentum is non-zero when the kernel runs
        upd, st = sampler.update(grads, st, params=params, rng=rng)
        p1 = core.apply_updates(params, upd)
        upd, st = sampler.update(1.3 * (p1 - 0.2), st, params=p1, rng=rng)
        outs[fused] = (np.asarray(core.apply_updates(p1, upd)), np.asarray(st.momentum))

    np.testing.assert_allclose(outs[True][0], outs[False][0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(outs[True][1], outs[False][1], rtol=1e-6, atol=1e-6)
