"""Every Pallas kernel compiles for a TPU v5e at the widths it serves.

Interpret mode (every other kernel test) runs the kernel body in Python and
checks none of the TPU's layout rules.  These tests hand the installed TPU
compiler a described ``v5e:2x2`` topology, with no chip attached, and
compile each kernel for one of its chips: a refused block shape, cast or
VMEM budget fails here, at no chip time.  Each compiled program must hold
the kernel itself (``tpu_custom_call``), not a fallback.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bma_select import bma_select
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_ecsghmc import LANES, fused_ec_update_flat, fused_precond_ec_update_flat
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rglru import rglru_scan

QWEN3_VOCAB = 151_936  # qwen3-0.6b
QWEN3_HEADS, QWEN3_KV_HEADS, QWEN3_HEAD_DIM = 16, 8, 128
RGEMMA_RNN_WIDTH = 2_560  # recurrentgemma-2b


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A program compiled for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (0.8, 50)], ids=["greedy", "sampled"])
@pytest.mark.parametrize("K", [2, 4])
def test_bma_select(one_chip, K, temperature, top_k):
    S, V = 8, QWEN3_VOCAB
    fn = functools.partial(bma_select, mode="probs", temperature=temperature,
                           top_k=top_k, interpret=False)
    _compile(fn, one_chip, ((K, S, V), jnp.float32), ((S, V), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_fused_ec_update_onchip_prng(one_chip, dtype):
    shape = (2048, LANES)
    hyper = dict(eps=1e-3, friction=0.1, mass=1.0, alpha=0.5, sigma_p=0.1)

    def fn(theta, p, g, c, seed):
        return fused_ec_update_flat(theta, p, g, c, seed=seed, interpret=False, **hyper)

    _compile(fn, one_chip, (shape, dtype), (shape, dtype), (shape, jnp.float32),
             (shape, dtype), ((1,), jnp.int32))


def test_fused_precond_ec_update_onchip_prng(one_chip):
    shape = (2048, LANES)
    hyper = dict(eps=1e-3, friction=0.1, alpha=0.5, sigma_p=0.1)

    def fn(theta, p, g, c, minv, seed):
        return fused_precond_ec_update_flat(
            theta, p, g, c, minv, seed=seed, interpret=False, **hyper
        )

    f32 = jnp.float32
    _compile(fn, one_chip, (shape, f32), (shape, f32), (shape, f32), (shape, f32),
             (shape, f32), ((1,), jnp.int32))


# serve.chat's paged decode: 20 slots, 160-page rows of 16 positions, a
# 1171-page pool, K=2 members
SLOTS, ROW_PAGES, BLOCK, POOL_PAGES, MEMBERS = 20, 160, 16, 1171, 2


def test_paged_attention(one_chip):
    G = QWEN3_HEADS // QWEN3_KV_HEADS
    fn = functools.partial(paged_attention, interpret=False)
    kv = ((POOL_PAGES, BLOCK, QWEN3_KV_HEADS, QWEN3_HEAD_DIM), jnp.bfloat16)
    hlo = _compile(fn, one_chip, ((SLOTS, QWEN3_KV_HEADS, G, QWEN3_HEAD_DIM), jnp.bfloat16),
                   kv, kv, ((SLOTS, ROW_PAGES), jnp.int32), ((SLOTS,), jnp.int32))
    assert "paged_attention" in hlo


def test_paged_decode_step_vmapped_in_layer_scan(one_chip, monkeypatch):
    """The engine's decode program: qwen3-0.6b's paged step vmapped over K=2
    members, the kernel inside the 28-layer scan, the pools viewed without
    a copy and never gathered whole."""
    from repro import configs
    from repro.kernels import ops
    from repro.models import get_model, init_params

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)  # compile the kernel, not its interpreter
    ops.paged_attention.clear_cache()
    cfg = configs.get_config("qwen3-0.6b").replace(
        param_dtype=jnp.float32, compute_dtype=jnp.bfloat16, use_flash_kernel=True
    )
    model = get_model(cfg)
    members = jax.eval_shape(lambda k: jax.vmap(
        lambda kk: init_params(model.param_specs(cfg), kk))(jax.random.split(k, MEMBERS)),
        jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: jax.vmap(
        lambda _: model.paged.make_pools(cfg, POOL_PAGES, BLOCK, jnp.bfloat16))(jnp.arange(MEMBERS)))
    place = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def step(members, pools, tokens, tables, ctx, write_block):
        return jax.vmap(lambda p, pool: model.paged.decode_step(
            cfg, p, pool, tokens, tables, ctx, write_block))(members, pools)

    hlo = jax.jit(step, donate_argnums=(1,)).lower(
        place(members), place(pools), i32(SLOTS, 1), i32(SLOTS, ROW_PAGES), i32(SLOTS),
        i32(SLOTS)).compile().as_text()
    ops.paged_attention.clear_cache()
    assert hlo.count("tpu_custom_call") == 1  # one kernel, in the layer loop
    # neither the whole-table page gather nor its relayout
    assert f"bf16[{MEMBERS},{SLOTS},{ROW_PAGES},{BLOCK}," not in hlo


def test_flash_attention(one_chip):
    B, S = 1, 1024
    fn = functools.partial(flash_attention, interpret=False)
    _compile(fn, one_chip, ((B, QWEN3_HEADS, S, QWEN3_HEAD_DIM), jnp.bfloat16),
             ((B, QWEN3_KV_HEADS, S, QWEN3_HEAD_DIM), jnp.bfloat16),
             ((B, QWEN3_KV_HEADS, S, QWEN3_HEAD_DIM), jnp.bfloat16))


def test_rglru_scan(one_chip):
    B, S, R = 2, 1024, RGEMMA_RNN_WIDTH
    fn = functools.partial(rglru_scan, interpret=False)
    f32 = jnp.float32
    _compile(fn, one_chip, ((B, S, R), f32), ((B, S, R), f32), ((B, R), f32))
