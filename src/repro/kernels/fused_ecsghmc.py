"""Fused EC-SGHMC update kernel (the paper-technique hot spot).

One VMEM pass per parameter block computes Eq. 6's chain update:

    theta' = theta + eps*M^-1*p                       (old momentum)
    p'     = (1 - eps*V*M^-1)*p - eps*g
             - eps*alpha*(theta - c_tilde) + sigma_p * N(0,1)

HBM traffic: 4 reads (theta, p, g, c̃) + 2 writes (theta', p') + noise bits.
XLA's unfused form re-reads theta for the coupling term, materializes the
Gaussian noise tensor in HBM, and round-trips p twice — ~9 tensor streams
vs. our 6.5 (the roofline win for the memory-bound sampler sweep).

Gaussian noise is derived in-register from uint32 bits via Box-Muller.
On real TPU the bits come from pltpu.prng_random_bits, seeded from the
caller's key and the block index (no HBM noise traffic at all); the
CPU-interpret validation path takes bits as an input so the pure-jnp oracle
sees identical randomness.  bf16 parameter stores use
STOCHASTIC ROUNDING (bits reused) — plain round-to-nearest bf16 MCMC biases
the stationary distribution at 1e-5-scale step sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 1024  # 8 sublanes x 128 lanes
BLOCK_ROWS = 8  # rows of LANES per grid step


def _bits_to_unit(bits):
    """uint32 -> uniform (0, 1) f32 using the top 24 bits.  ``bits >> 8``
    fits in int32, and Mosaic converts int32 (not uint32) to f32."""
    top = (bits >> 8).astype(jnp.int32)
    return top.astype(jnp.float32) * (1.0 / (1 << 24)) + (0.5 / (1 << 24))


def _box_muller(bits1, bits2):
    u1 = _bits_to_unit(bits1)
    u2 = _bits_to_unit(bits2)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos((2.0 * jnp.pi) * u2)


def _stochastic_round_bf16(x_f32, bits):
    """f32 -> bf16 with probability proportional to proximity."""
    xi = jax.lax.bitcast_convert_type(x_f32, jnp.uint32)
    xi = xi + (bits & jnp.uint32(0xFFFF))  # add uniform in [0, 2^16)
    xi = xi & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(xi, jnp.float32).astype(jnp.bfloat16)


def _noise_bits(noise_refs, shape):
    """Two uint32 bit blocks: drawn on chip from the SMEM seed mixed with the
    block index (Mosaic takes at most two seed words), or read from the two
    streamed bit inputs."""
    if len(noise_refs) == 1:
        pltpu.prng_seed(noise_refs[0][0], pl.program_id(0))
        draw = lambda: jax.lax.bitcast_convert_type(pltpu.prng_random_bits(shape), jnp.uint32)
        return draw(), draw()
    return noise_refs[0][...], noise_refs[1][...]


def _store(theta_out_ref, p_out_ref, theta_new, p_new, bits1, bits2, stochastic_round):
    if stochastic_round and theta_out_ref.dtype == jnp.bfloat16:
        sr_bits = bits1 ^ bits2
        theta_out_ref[...] = _stochastic_round_bf16(theta_new, sr_bits)
        p_out_ref[...] = _stochastic_round_bf16(p_new, jnp.uint32(0x9E3779B9) ^ sr_bits)
    else:
        theta_out_ref[...] = theta_new.astype(theta_out_ref.dtype)
        p_out_ref[...] = p_new.astype(p_out_ref.dtype)


def _kernel(scal_ref, theta_ref, p_ref, g_ref, c_ref, *refs, stochastic_round: bool):
    """``scal_ref`` SMEM (5,): eps_minv, decay, eps, coupling, sigma_p.
    ``refs``: the noise input(s) — an SMEM int32 seed (1,) or two uint32 bit
    blocks — then theta', p'."""
    *noise_refs, theta_out_ref, p_out_ref = refs
    eps_minv = scal_ref[0]
    decay = scal_ref[1]
    eps = scal_ref[2]
    coupling = scal_ref[3]
    sigma_p = scal_ref[4]

    theta = theta_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    bits1, bits2 = _noise_bits(noise_refs, theta.shape)

    noise = _box_muller(bits1, bits2)
    theta_new = theta + eps_minv * p
    p_new = decay * p - eps * g - coupling * (theta - c) + sigma_p * noise
    _store(theta_out_ref, p_out_ref, theta_new, p_new, bits1, bits2, stochastic_round)


def _block():
    return pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))


def _noise_operands(bits1, bits2, seed):
    """(in_specs, operands) of the noise input: the on-chip seed in SMEM, or
    the two streamed bit tensors.  Exactly one of the two must be given."""
    if (seed is None) == (bits1 is None or bits2 is None):
        raise ValueError("pass either seed (on-chip PRNG) or both bits1 and bits2")
    if seed is not None:
        return [pl.BlockSpec(memory_space=pltpu.SMEM)], [seed.astype(jnp.int32)]
    return [_block(), _block()], [bits1, bits2]


def _call(kernel, scalars, tensors, bits1, bits2, seed, *, stochastic_round, interpret):
    theta, p = tensors[0], tensors[1]
    R, L = theta.shape
    assert L == LANES and R % BLOCK_ROWS == 0, (theta.shape,)
    noise_specs, noise = _noise_operands(bits1, bits2, seed)
    return pl.pallas_call(
        functools.partial(kernel, stochastic_round=stochastic_round),
        grid=(R // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [_block() for _ in tensors]
        + noise_specs,
        out_specs=(_block(), _block()),
        out_shape=(
            jax.ShapeDtypeStruct(theta.shape, theta.dtype),
            jax.ShapeDtypeStruct(p.shape, p.dtype),
        ),
        interpret=interpret,
    )(scalars, *tensors, *noise)


def fused_ec_update_flat(
    theta,
    p,
    g,
    c_tilde,
    bits1=None,
    bits2=None,
    *,
    seed=None,
    eps: float,
    friction: float,
    mass: float,
    alpha: float,
    sigma_p: float,
    stochastic_round: bool = True,
    interpret: bool = True,
):
    """Core entry: all operands (R, LANES)-shaped, R % BLOCK_ROWS == 0.
    Hyperparameters may be traced (they travel via SMEM).  Noise comes from
    the uint32 tensors ``bits1``/``bits2``, or, with ``seed`` (int32 (1,)),
    from the TPU's on-chip PRNG seeded by it and the block index."""
    minv = 1.0 / mass
    scalars = jnp.stack(
        [
            jnp.asarray(eps * minv, jnp.float32),
            jnp.asarray(1.0 - eps * friction * minv, jnp.float32),
            jnp.asarray(eps, jnp.float32),
            jnp.asarray(eps * alpha, jnp.float32),
            jnp.asarray(sigma_p, jnp.float32),
        ]
    )
    return _call(
        _kernel, scalars, (theta, p, g, c_tilde), bits1, bits2, seed,
        stochastic_round=stochastic_round, interpret=interpret,
    )


def _precond_kernel(
    scal_ref, theta_ref, p_ref, g_ref, c_ref, minv_ref, *refs, stochastic_round: bool
):
    """Preconditioned Eq. 6 chain update — ``_kernel`` with the scalar
    eps*M^-1 / decay pair replaced by a streamed diagonal M^-1:

        theta' = theta + (eps*M^-1) * p
        p'     = (1 - ef*M^-1)*p - eps*g - coupling*(theta - c̃) + sigma_p*n

    ``scal_ref`` SMEM (4,): eps, ef (= eps*V), coupling (= eps*alpha),
    sigma_p; ``minv_ref`` the per-element M^-1 block (frozen diagonal
    preconditioner); ``refs`` as in ``_kernel``.

    Term grouping mirrors ``core.ec_sghmc.p_step`` with an ARRAY ``minv``
    (ef*minv, then 1 - ·), so fused and unfused agree bit-for-bit in f32 —
    pinned by tests/test_fused_equivalence.py.  One extra HBM read stream
    (M^-1) vs. the plain kernel; still beats XLA's ~10 streams."""
    *noise_refs, theta_out_ref, p_out_ref = refs
    eps = scal_ref[0]
    ef = scal_ref[1]
    coupling = scal_ref[2]
    sigma_p = scal_ref[3]

    theta = theta_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    minv = minv_ref[...].astype(jnp.float32)
    bits1, bits2 = _noise_bits(noise_refs, theta.shape)

    noise = _box_muller(bits1, bits2)
    theta_new = theta + eps * minv * p
    p_new = (1.0 - ef * minv) * p - eps * g - coupling * (theta - c) + sigma_p * noise
    _store(theta_out_ref, p_out_ref, theta_new, p_new, bits1, bits2, stochastic_round)


def fused_precond_ec_update_flat(
    theta,
    p,
    g,
    c_tilde,
    minv,
    bits1=None,
    bits2=None,
    *,
    seed=None,
    eps: float,
    friction: float,
    alpha: float,
    sigma_p: float,
    stochastic_round: bool = True,
    interpret: bool = True,
):
    """Preconditioned entry: operands (R, LANES)-shaped, R % BLOCK_ROWS == 0,
    ``minv`` elementwise (the frozen diagonal M^-1).  Hyperparameters may be
    traced (SMEM); the diagonal streams as a tensor block.  Noise as in
    :func:`fused_ec_update_flat`."""
    assert minv.shape == theta.shape, (minv.shape, theta.shape)
    scalars = jnp.stack(
        [
            jnp.asarray(eps, jnp.float32),
            jnp.asarray(eps * friction, jnp.float32),
            jnp.asarray(eps * alpha, jnp.float32),
            jnp.asarray(sigma_p, jnp.float32),
        ]
    )
    return _call(
        _precond_kernel, scalars, (theta, p, g, c_tilde, minv), bits1, bits2, seed,
        stochastic_round=stochastic_round, interpret=interpret,
    )
