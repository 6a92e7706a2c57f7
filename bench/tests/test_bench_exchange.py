"""The coupled sampler's reference step against the program's update, the
collective counter on a compiled program, and the exchange's exposed time
read from a small trace worked by hand."""
import importlib.util
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import collectives
import harness
import trace_reduce as tr
from reference import sgmcmc

K, S_SYNC = 4, 4
DEP = {"step_size": 0.05, "friction": 1.0, "mass": 1.0, "noise_convention": "eq6"}
COUPLING = {"alpha": 1.0, "center_friction": 1.0, "center_noise_in_p": True}


def _program_sampler(state_dtype):
    from repro import core

    return core.ec_sghmc(step_size=DEP["step_size"], alpha=COUPLING["alpha"],
                         friction=DEP["friction"], center_friction=COUPLING["center_friction"],
                         mass=DEP["mass"], sync_every=S_SYNC, noise_convention="eq6",
                         state_dtype=state_dtype, per_chain_noise=True)


def _reference_step(sync, theta, p, centre, g, key):
    """K chains of ``sgmcmc.ec_sghmc_step``, the chain mean a pmean over a
    vmapped axis, the noise drawn by the deployment's key convention."""
    k_p, k_r = jax.random.split(key)
    n_r = sgmcmc.tree_normal(k_r, centre[1])

    def one(i, th, pp, gg):
        n_p = sgmcmc.tree_normal(jax.random.fold_in(k_p, i), pp)
        return sgmcmc.ec_sghmc_step(DEP, COUPLING, (th, pp), centre, gg, (n_p, n_r), sync,
                                    lambda x: jax.lax.pmean(x, "chain"))

    (th, pp), c = jax.vmap(one, in_axes=(0, 0, 0, 0), out_axes=((0, 0), None),
                           axis_name="chain")(jnp.arange(K), theta, p, g)
    return th, pp, c


@pytest.mark.parametrize("state_dtype,tol", [(jnp.float32, 1e-6), (jnp.bfloat16, 1e-2)])
def test_ec_sghmc_step_follows_the_program(state_dtype, tol):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    theta = {"w": jax.random.normal(keys[0], (K, 3, 5)), "b": jax.random.normal(keys[1], (K, 7))}
    target = jax.tree.map(lambda x: 0.5 * x[0], theta)
    sampler = _program_sampler(state_dtype)
    state = sampler.init(theta)
    th_p, st = theta, state
    th_r, p_r = theta, jax.tree.map(lambda x: jnp.zeros(x.shape, state_dtype), theta)
    # the centre starts at the chains' mean, stored in the state dtype
    c0 = jax.tree.map(lambda x: x.astype(state_dtype).astype(jnp.float32).mean(0)
                      .astype(state_dtype), theta)
    centre = (c0, jax.tree.map(jnp.zeros_like, c0), c0, c0)
    grad = lambda th: jax.tree.map(lambda x, m: x - m, th, target)
    base = jax.random.key(1)
    for t in range(S_SYNC + 1):
        key = jax.random.fold_in(base, t)
        upd, st = sampler.update(grad(th_p), st, th_p, key)
        th_p = jax.tree.map(lambda a, u: a + u, th_p, upd)
        th_r, p_r, centre = _reference_step((t + 1) % S_SYNC == 0, th_r, p_r, centre,
                                            grad(th_r), key)
        got = (th_p, st.momentum, st.center, st.center_momentum, st.center_stale,
               st.mean_theta_stale)
        want = (th_r, p_r) + tuple(centre)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                       rtol=tol, atol=tol)
        moved = not np.allclose(np.asarray(st.mean_theta_stale["w"], np.float32),
                                np.asarray(c0["w"], np.float32))
        assert moved == (t + 1 >= S_SYNC), t  # the exchange refreshed the stale mean at step s


def _compiled_text(stray: bool) -> str:
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("chain",))

    def f(x, step):
        x = jax.lax.cond((step + 1) % S_SYNC == 0, lambda: jax.lax.pmean(x, "chain"),
                         lambda: x)
        return x + jax.lax.psum(jnp.sum(x), "chain") if stray else x

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("chain"), P()), out_specs=P("chain"),
                           check_vma=False))
    return fn.lower(jnp.ones((1, 8)), 3).compile().as_text()


@pytest.mark.parametrize("stray,want", [
    (False, {"all_reduce": 1, "in_sync_branch": 1, "other": 0}),
    (True, {"all_reduce": 2, "in_sync_branch": 1, "other": 0}),
])
def test_collectives_of_a_compiled_program(stray, want):
    text = _compiled_text(stray)
    assert collectives.count(text) == want
    assert collectives.exchange_checks(text) == {"stray_collectives": int(stray),
                                                 "missing_exchange": 0}


def test_collectives_by_hand():
    text = "\n".join([
        '  %a = f32[8] all-reduce-start(%x), metadata={op_name="jit(f)/cond/branch_1_fun/pmean"}',
        '  %b = f32[8] all-reduce-done(%a)',
        '  %c = f32[8] all-gather(%y), metadata={op_name="jit(f)/all_gather"}',
        '  %d = f32[8] add(%x, %y)',
    ])
    assert collectives.count(text) == {"all_reduce": 1, "in_sync_branch": 1, "other": 1}
    assert collectives.exchange_checks("") == {"stray_collectives": 0, "missing_exchange": 1}


def _reader():
    path = harness.BENCH / "metrics" / "exchange_exposed_ms.py"
    spec = importlib.util.spec_from_file_location("exchange_exposed_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# two chips, a window of 1000 ns from t=0; each chip runs one exchange
# (all-reduce) beside which a fusion runs for part of its time
PLANES = {
    "/host:CPU": {"python": [("bench.window", 0, 1000)]},
    "/device:TPU:0": {"XLA Ops": [("fusion.1", 0, 600), ("all-reduce.1", 600, 800),
                                  ("fusion.2", 650, 700)]},
    "/device:TPU:1": {"XLA Ops": [("fusion.1", 0, 500), ("all-reduce.1", 500, 900)]},
}


def test_exchange_exposed_ms_by_hand():
    reduced = tr.reduce_planes(PLANES, num_devices=2)
    # chip 0: 200 ns of all-reduce, 50 of them beside fusion.2; chip 1: 400
    assert reduced["collective_exposed_s"] == pytest.approx((150e-9 + 400e-9) / 2)
    ctx = {"trace": reduced, "steps": 8, "mix": {"sync_every": 4}}
    # 8 steps hold 2 exchanges
    assert _reader().read(ctx) == pytest.approx(1000.0 * 275e-9 / 2)


def test_exchange_exposed_ms_reads_nothing_without_an_exchange():
    planes = {"/host:CPU": PLANES["/host:CPU"], "/device:TPU:0": {"XLA Ops": [("fusion.1", 0, 600)]}}
    ctx = {"trace": tr.reduce_planes(planes), "steps": 8, "mix": {"sync_every": 4}}
    assert _reader().read(ctx) is None
    assert _reader().read(dict(ctx, mix={})) is None


def test_a_mix_without_its_limits_is_refused():
    """ec-4chain.json states no gap limits until they are read on the chip:
    a run of it stops before any device work, naming what is missing."""
    import sample
    import tiny

    cell = tiny.sample_cell("ec-4chain")
    cell["mix"]["check"] = json.loads((tiny.BENCH / "traffic/ec-4chain.json").read_text())["check"]
    with pytest.raises(harness.CellError, match="no limit for grad_gap, change_gap"):
        sample.run(cell, 1, 1.0, False, [None] * 4, 0.0, None)
