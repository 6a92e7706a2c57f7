"""A sampling cell: SGHMC chains over the model's posterior, one chain a
chip.  Plain SGHMC (``sampler`` "sghmc") runs through ``ChainExecutor.run``;
the elastically coupled sampler (``sampler`` "ec_sghmc", the chains sharing
a centre they exchange with every ``sync_every`` steps) runs through
``ChainExecutor.run_sharded`` over the (chain,) mesh of the cell's chips.

Set-up makes the chains from the seed, builds the executor and its state,
and drives that same object through its first steps (3; the coupled
sampler's up to its first exchange, at step ``sync_every``), one call of
the window's own kind per step, keeping what the check reads: the momentum
after step 1, the positions after the last, each step's loss where the
program reports it, and for the coupled sampler, after the exchange, the
stale mean of the chains and the centre's momentum and stale position.  The coupled sampler's compiled
step is also read for its collectives.  The window then runs whole units of
``unit_steps`` steps, each ended by ``block_until_ready``.  Afterwards the
float32 reference follows the same steps and the two are compared, each
number against the limit the mix's ``check`` block gives it.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import collectives
import flops
import harness
import traffic
import weights
from harness import note
from reference import sgmcmc

# the compared numbers: the relative gap of each of the first three steps'
# loss; the worst leaf's gap of the first gradient's norm, worked out from
# the state; and of the norm of the positions' change over three steps.
# Their limits are the mix's (``check.limits``); PERF.md gives the readings
# they were set from
CHECKS = ("loss_gap", "grad_gap", "change_gap")
# the coupled sampler's, over the steps up to the first exchange:
# ``run_sharded`` reports no loss; the worst leaf's gap of the norm of the
# chains' stale mean after the exchange; of the centre's momentum and stale
# position (``centre_gap``, the larger of the two); and the compiled step's
# collectives (``collectives.exchange_checks``)
COUPLED_CHECKS = ("grad_gap", "change_gap", "exchange_gap", "centre_gap",
                  "stray_collectives", "missing_exchange")
FIRST_STEPS = 3  # driven through the window's call, then followed by the reference
CHAIN = "chain"  # the mesh axis the chains are laid out on
# the token stream's key is fixed and a run's seed picks where in the stream
# its chains start: keys closed over by a traced function become constants
# of the compiled program, so a seed-dependent key would compile every run
DATA_KEY_STREAM = 2


def start_step(seed: int) -> int:
    """The absolute step a seed's chains start at: where its minibatches
    begin in the stream, and what its noise keys fold in."""
    return (seed % (1 << 20)) << 10


# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone and is left out of the change
NOUGHT = 1e-3


def coupled(mix: dict) -> bool:
    return mix["sampler"] == "ec_sghmc"


def check_steps(mix: dict) -> int:
    """How many steps the check follows: the coupled sampler's up to its
    first exchange, which ends step ``sync_every``."""
    return int(mix["sync_every"]) if coupled(mix) else FIRST_STEPS


def checks_of(mix: dict) -> tuple:
    return COUPLED_CHECKS if coupled(mix) else CHECKS


def model_config(cfg: dict):
    import serve

    return serve.model_config(cfg).replace(remat=cfg["deployment"]["remat"])


def make_sampler(dep: dict, mix: dict):
    import jax.numpy as jnp

    from repro import core

    if coupled(mix):
        return core.ec_sghmc(step_size=dep["step_size"], alpha=mix["alpha"],
                             friction=dep["friction"], center_friction=mix["center_friction"],
                             mass=dep["mass"], sync_every=mix["sync_every"],
                             noise_convention=dep["noise_convention"],
                             center_noise_in_p=mix["center_noise_in_p"], chain_axis=CHAIN,
                             state_dtype=jnp.dtype(dep["state_dtype"]))
    if mix["sampler"] != "sghmc":
        raise harness.CellError(f"sampler {mix['sampler']!r} is not wired into the benchmark")
    return core.sghmc(step_size=dep["step_size"], friction=dep["friction"], mass=dep["mass"],
                      noise_convention=dep["noise_convention"],
                      state_dtype=jnp.dtype(dep["state_dtype"]))


class Chains:
    """The system under test: the executor, its carry and its call."""

    def __init__(self, cfg: dict, mix: dict, seed: int, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.distributed.sharding import chain_specs
        from repro.models import get_model
        from repro.run import ChainExecutor
        from repro.train.step import make_grad_fn

        dep = cfg["deployment"]
        mcfg = model_config(cfg)
        model = get_model(mcfg)
        weights.check_layout(cfg, model.param_specs(mcfg))
        self.K = mix["chains"]
        self.mix, self.mesh = mix, mesh
        data_key = jax.random.key(DATA_KEY_STREAM)
        self.key = harness.seed_key(seed, 3)
        B, S, V = mix["batch"], mix["seq"], cfg["vocab_size"]

        if coupled(mix):
            local = self.K // mesh.shape[CHAIN]

            def batch_fn(t):
                # traced inside shard_map: this shard's chains
                first = jax.lax.axis_index(CHAIN) * local
                return jax.vmap(lambda c: traffic.token_batch(data_key, t, c, B, S, V))(
                    first + jax.numpy.arange(local))
        else:
            def batch_fn(t):
                return jax.vmap(lambda c: traffic.token_batch(data_key, t, c, B, S, V))(
                    jax.numpy.arange(self.K))

        self.sampler = make_sampler(dep, mix)
        self.ex = ChainExecutor(
            sampler=self.sampler,
            grad_fn=make_grad_fn(mcfg, model, n_data=dep["n_data"], weight_decay=dep["weight_decay"]),
            device_batch_fn=batch_fn, chunk_steps=1, key_mode="fold")
        self.params = weights.make(cfg, seed, self.K, out_sharding=NamedSharding(mesh, P("chain")))
        if coupled(mix):
            # laid out as run_sharded takes it: a chain's momentum on its
            # chip, the centre replicated (left to the compiler, the zero
            # momentum comes out replicated, four chains' worth a chip)
            specs = chain_specs(jax.eval_shape(self.sampler.init, self.params), self.K, CHAIN)
            self.state = jax.jit(self.sampler.init, out_shardings=jax.tree.map(
                lambda s: NamedSharding(mesh, s), specs))(self.params)
        else:
            self.state = self.sampler.init(self.params)
        self.t = start_step(seed)

    def step(self, n: int):
        """Advance ``n`` steps in one call; returns the loss the program
        reports for the last of them (None from ``run_sharded``, which
        reports none)."""
        if coupled(self.mix):
            res = self.ex.run_sharded(self.params, self.state, num_steps=n, key=self.key,
                                      mesh=self.mesh, chain_axis=CHAIN, start_step=self.t)
        else:
            res = self.ex.run(self.params, self.state, num_steps=n, key=self.key,
                              start_step=self.t)
        self.params, self.state, self.t = res.params, res.state, self.t + n
        return res.metrics.get("nll_per_token")

    def exchange_checks(self) -> dict:
        """The collectives of the compiled step the window runs (one step a
        chunk), read once from its HLO text: the executor's own jitted chunk,
        lowered again at the carry it was called with, finds the executable
        it already compiled."""
        chunk = self.ex._compiled[("sharded", 1, CHAIN, id(self.mesh))]
        carry = self.ex._sharded_carry(self.params, self.state, self.t)
        return collectives.exchange_checks(chunk.lower(self.key, carry).compile().as_text())


def to_host(tree):
    import jax

    return jax.tree.map(np.asarray, jax.device_get(tree))


def first_steps(chains: Chains) -> dict:
    """The first steps through the window's call, and what the check reads."""
    n = check_steps(chains.mix)
    seen = {"loss": []}
    for i in range(n):
        loss = chains.step(1)
        if loss is not None:
            seen["loss"].append(float(loss))
        if i == 0:
            seen["p1"] = to_host(chains.state.momentum)
        if i == n - 1:
            seen["theta"] = to_host(chains.params)
            if coupled(chains.mix):
                st = chains.state
                seen.update(mean_stale=to_host(st.mean_theta_stale),
                            centre_r=to_host(st.center_momentum),
                            centre_stale=to_host(st.center_stale))
    return seen


def warm_unit(chains: Chains, unit: int) -> None:
    """One whole unit, as the window runs it: a unit's later steps are fed
    the carry its first step produced, a lowering of their own."""
    import jax

    chains.step(unit)
    jax.block_until_ready(chains.params)


def gap(prog: np.ndarray, refv: np.ndarray, keep=None) -> float:
    """Worst (chain, leaf) gap between two per-leaf norms, against the
    reference's norm of that leaf or the median leaf's, whichever is
    larger."""
    prog, refv = np.asarray(prog, np.float64), np.asarray(refv, np.float64)
    if keep is not None:
        prog, refv = prog[keep], refv[keep]
    if refv.size == 0:
        return float("inf")
    scale = np.maximum(refv, np.median(refv))
    return float(np.max(np.abs(prog - refv) / scale))


def _reference_parts(cfg: dict, mix: dict, mesh, precision: str, rows):
    """What the reference walks share, on the cell's mesh with one chain a
    shard: a jitted ``shard_map``, a chain's leaves without and with their
    chain axis, per-leaf norms, and the reference's gradient and loss (the
    chains' mean) at step t."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    ref = harness.reference(cfg)
    dep = cfg["deployment"]
    data_key = jax.random.key(DATA_KEY_STREAM)
    B, S, V = mix["batch"], mix["seq"], cfg["vocab_size"]

    def smap(fn, in_specs, out_specs, donate=()):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                                 check_vma=False), donate_argnums=donate)

    sq = lambda tree: jax.tree.map(lambda x: x[0], tree)
    ex = lambda tree: jax.tree.map(lambda x: x[None], tree)
    norms = lambda tree: jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                                    for x in jax.tree.leaves(tree)])[None]

    def grad_fn(theta, t):
        i = jax.lax.axis_index(CHAIN)
        batch = traffic.token_batch(data_key, t, i, B, S, V)
        _, loss, g = ref.potential_and_grad(cfg, sq(theta), batch, n_data=dep["n_data"],
                                            weight_decay=dep["weight_decay"],
                                            precision=precision, rows=rows)
        return ex(g), jax.lax.pmean(loss, CHAIN)

    grad = smap(grad_fn, (P(CHAIN), P()), (P(CHAIN), P()))
    return smap, sq, ex, norms, grad


class _PlainWalk:
    """SGHMC (``sgmcmc.sghmc_step``): the state is (p,)."""

    def __init__(self, dep: dict, mix: dict, mesh, fault, parts):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        smap, _sq, _ex, norms, _grad = parts
        self.sd = jnp.dtype(dep["state_dtype"])
        self.chain = NamedSharding(mesh, P(CHAIN))
        eps = dep["step_size"]
        sigma = eps * np.sqrt(2.0 * dep["friction"])

        def noise_of(p, t, key):
            """The chain's standard normal draw at step t, by the
            deployment's key convention (``key`` is the run's key): one
            draw over the (1, ...) leaves."""
            return sgmcmc.tree_normal(jax.random.fold_in(key, t), p)

        def step_fn(theta, p, g, t, key):
            if fault == "unchanged":
                return theta, p
            return sgmcmc.sghmc_step(dep, theta, p, g, noise_of(p, t, key))

        def recon_fn(p1, t, key):
            """The first gradient, worked out from the momentum after step
            1 (the momentum starts at zero)."""
            n0 = noise_of(p1, t, key)
            return norms(jax.tree.map(lambda m, n: -(m.astype(jnp.float32) - sigma * n) / eps,
                                      p1, n0))

        self._step = smap(step_fn, (P(CHAIN),) * 3 + (P(), P()), (P(CHAIN), P(CHAIN)),
                          donate=(0, 1, 2))
        self._recon = smap(recon_fn, (P(CHAIN), P(), P()), P(CHAIN))

    def init(self, theta):
        import jax
        import jax.numpy as jnp

        return (jax.tree.map(lambda x: jnp.zeros(x.shape, self.sd, device=self.chain), theta),)

    def grad_norms(self, p1, theta0, state0, t, key):
        return self._recon(p1, t, key)

    def step(self, i, theta, state, g, t, key):
        theta, p = self._step(theta, state[0], g, t, key)
        return theta, (p,), (self._recon(p, t, key) if i == 0 else None)

    def finish(self, state, seen, out, own) -> None:
        pass


class _CoupledWalk:
    """Elastically coupled SGHMC (``sgmcmc.ec_sghmc_step``): the state is
    (p, c, r, c~, mean~), the centre's four trees replicated on every chip
    as the program holds them, the exchange's mean a ``pmean`` over the
    chain axis.  The first gradient is worked out inside the first step,
    which takes the positions at step 0 (donated, to fit beside the centre)."""

    def __init__(self, dep: dict, mix: dict, mesh, fault, parts):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        smap, sq, ex, norms, _grad = parts
        self.sd = sd = jnp.dtype(dep["state_dtype"])
        self.chain, self.rep = NamedSharding(mesh, P(CHAIN)), NamedSharding(mesh, P())
        self.s = int(mix["sync_every"])
        eps, alpha = dep["step_size"], mix["alpha"]
        sigma_p, _ = sgmcmc.ec_noise_scales(dep, mix)
        pmean = lambda x: jax.lax.pmean(x, CHAIN)
        chain_mean = (lambda x: x) if fault == "own_chain_mean" else pmean

        def noise_of(p, r, t, key):
            """The chain's and the centre's standard normal draws at step t."""
            k_p, k_r = jax.random.split(jax.random.fold_in(key, t))
            k_p = jax.random.fold_in(k_p, jax.lax.axis_index(CHAIN))
            return sgmcmc.tree_normal(k_p, p), sgmcmc.tree_normal(k_r, r)

        def grad_from(p1, theta0, c0, n0):
            """The first gradient, worked out from the momentum after step
            1 (the momentum starts at zero)."""
            return jax.tree.map(lambda m, th, c, nn: -(m.astype(jnp.float32) - sigma_p * nn) / eps
                                - alpha * (th - c.astype(jnp.float32)), p1, theta0, c0, n0)

        def step_fn(sync, first, theta, p, c, r, c_st, m_st, g, t, key):
            """One step; on the first, also the norms of the first gradient
            worked out from its momentum."""
            noise = noise_of(sq(p), r, t, key)
            if fault == "unchanged":
                (th, pp), centre = (sq(theta), sq(p)), (c, r, c_st, m_st)
            else:
                (th, pp), centre = sgmcmc.ec_sghmc_step(dep, mix, (sq(theta), sq(p)),
                                                        (c, r, c_st, m_st), sq(g), noise, sync,
                                                        chain_mean)
            if fault == "centre_frozen":
                centre = (c, r, c if sync else c_st, centre[3])
            outs = (ex(th), ex(pp)) + tuple(centre)
            if first:
                outs += (norms(grad_from(pp, sq(theta), c_st, noise[0])),)
            return outs

        def make_step(sync, first):
            ins = (P(CHAIN), P(CHAIN)) + (P(),) * 4 + (P(CHAIN), P(), P())
            outs = (P(CHAIN), P(CHAIN)) + (P(),) * 4 + ((P(CHAIN),) if first else ())
            return smap(lambda *a: step_fn(sync, first, *a), ins, outs, donate=tuple(range(7)))

        self._steps = {sig: make_step(*sig) for sig in
                       ((False, True), (False, False), (True, False), (True, True))}
        self._prog_grad = smap(
            lambda p1, theta0, c0, t, key: norms(grad_from(sq(p1), sq(theta0), c0,
                                                           noise_of(sq(p1), c0, t, key)[0])),
            (P(CHAIN), P(CHAIN), P(), P(), P()), P(CHAIN))
        self._start = smap(lambda th: jax.tree.map(
            lambda x: pmean(x[0].astype(sd).astype(jnp.float32)).astype(sd), th), P(CHAIN), P())
        self._norms = smap(norms, P(), P())

    def init(self, theta):
        import jax
        import jax.numpy as jnp

        c = self._start(theta)  # the centre starts at the chains' mean
        copy = lambda tree: jax.tree.map(jnp.copy, tree)
        zeros = lambda tree, at: jax.tree.map(lambda x: jnp.zeros(x.shape, self.sd, device=at),
                                              tree)
        return (zeros(theta, self.chain), c, zeros(c, self.rep), copy(c), copy(c))

    def grad_norms(self, p1, theta0, state0, t, key):
        return self._prog_grad(p1, theta0, state0[3], t, key)

    def step(self, i, theta, state, g, t, key):
        res = self._steps[((i + 1) % self.s == 0, i == 0)](theta, *state, g, t, key)
        return res[0], tuple(res[1:6]), (res[6] if i == 0 else None)

    def finish(self, state, seen, out, own) -> None:
        """The exchange's and the centre's norms: the stale mean of the
        chains, and the centre's momentum and stale position."""
        import jax

        _p, _c, r, c_st, m_st = state
        kept = {"mean_stale": m_st, "centre_r": r, "centre_stale": c_st}
        ref = {k: np.asarray(self._norms(v)) for k, v in kept.items()}
        out["exchange_ref"] = ref["mean_stale"]
        out["centre_ref"] = [ref["centre_r"], ref["centre_stale"]]
        if own is not None:
            own.update({k: to_host(v) for k, v in kept.items()})
        if seen:
            prog = {k: np.asarray(self._norms(jax.device_put(seen[k], self.rep))) for k in kept}
            out["exchange_prog"] = prog["mean_stale"]
            out["centre_prog"] = [prog["centre_r"], prog["centre_stale"]]


def follow(cfg: dict, mix: dict, seed: int, mesh, seen: dict | None, precision: str = "f32",
           fault: str | None = None, record: bool = False) -> dict:
    """The reference over the first steps (``check_steps``) on the cell's
    mesh, and the per-leaf norms it reads of itself and of ``seen`` (what
    the system under test kept).  With ``precision``/``fault`` it is
    instead the control or a planted fault, and ``record`` keeps what it
    produced in the shape of ``seen``, to be put in the program's place.
    ``fault``: "unchanged" (the step returns its state), "half_batch" (the
    loss over the first half of the rows), and for the coupled sampler
    "own_chain_mean" (the exchange's mean taken over the chip's own chain,
    without the collective) and "centre_frozen" (the centre's position and
    momentum left as they are, its noise undrawn).  The positions at step
    0 are made again from the seed for the change."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    dep = cfg["deployment"]
    run_key = harness.seed_key(seed, 3)
    t0, n = start_step(seed), check_steps(mix)
    chain = NamedSharding(mesh, P(CHAIN))
    rows = mix["batch"] // 2 if fault == "half_batch" else None
    parts = _reference_parts(cfg, mix, mesh, precision, rows)
    smap, norms, grad = parts[0], parts[3], parts[4]
    walk = (_CoupledWalk if coupled(mix) else _PlainWalk)(dep, mix, mesh, fault, parts)
    change = smap(lambda a, b: norms(jax.tree.map(lambda x, y: x - y, a, b)),
                  (P(CHAIN), P(CHAIN)), P(CHAIN))
    leafnorms = smap(norms, P(CHAIN), P(CHAIN))

    theta = weights.make(cfg, seed, mix["chains"], out_sharding=chain)
    state = walk.init(theta)
    out = {"loss": [], "seen_loss": seen["loss"][:n] if seen else []}
    own = {"loss": []} if record else None
    if seen:
        out["grad_prog"] = np.asarray(walk.grad_norms(jax.device_put(seen["p1"], chain), theta,
                                                      state, jnp.int32(t0), run_key))
    for i in range(n):
        g, loss = grad(theta, jnp.int32(t0 + i))
        out["loss"].append(float(loss))
        if record:
            own["loss"].append(float(loss))
        if i == 0:
            out["grad0"] = np.asarray(leafnorms(g))
        theta, state, grad_ref = walk.step(i, theta, state, g, jnp.int32(t0 + i), run_key)
        del g
        if i == 0:
            out["grad_ref"] = np.asarray(grad_ref)
            if record:
                own["p1"] = to_host(state[0])
    walk.finish(state, seen, out, own)
    del state
    theta0 = weights.make(cfg, seed, mix["chains"], out_sharding=chain)
    out["change_ref"] = np.asarray(change(theta, theta0))
    if record:
        own["theta"] = to_host(theta)
        out["own"] = own
    if seen:
        del theta
        out["change_prog"] = np.asarray(change(jax.device_put(seen["theta"], chain), theta0))
    return out


def readings(r: dict) -> dict:
    """The compared numbers from ``follow``'s norms."""
    g0 = r["grad0"]
    keep = g0 >= NOUGHT * np.median(g0)
    out = {
        "grad_gap": gap(r["grad_prog"], r["grad_ref"]),
        "change_gap": gap(r["change_prog"], r["change_ref"], keep),
        "left_out": int(np.sum(~keep)),
    }
    if r["seen_loss"]:
        out["loss_gap"] = max(abs(a - b) / abs(b) for a, b in zip(r["seen_loss"], r["loss"]))
    if "exchange_ref" in r:
        out["exchange_gap"] = gap(r["exchange_prog"], r["exchange_ref"])
        out["centre_gap"] = max(gap(a, b) for a, b in zip(r["centre_prog"], r["centre_ref"]))
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, devices, t_start: float,
        compiles) -> dict:
    import jax
    from jax.sharding import Mesh

    cfg, mix = cell["cfg"], cell["mix"]
    if mix["chains"] != cell["chips"]:
        raise harness.CellError("a sampling cell runs one chain per chip")
    missing = [name for name in checks_of(mix) if name not in mix["check"]["limits"]]
    if missing:
        raise harness.CellError(f"no limit for {', '.join(missing)} in the mix's check.limits: "
                                "limits are set from chip readings (bench/calibrate.py)")
    mesh = Mesh(np.asarray(devices), ("chain",))
    chains = Chains(cfg, mix, seed, mesh)
    seen = first_steps(chains)
    warm_unit(chains, mix["unit_steps"])
    t_structure = time.perf_counter()
    structure = chains.exchange_checks() if coupled(mix) else {}
    window_s = min(seconds, harness.TRACE_SECONDS) if trace else seconds
    note(f"first {check_steps(mix)} steps done, losses {seen['loss']}; structure read in "
         f"{time.perf_counter() - t_structure:.2f} s; {compiles}")
    compiles.mark()
    setup_s = harness.elapsed(t_start)
    unit = mix["unit_steps"]
    units, window = 0, 0.0
    with harness.Profile(trace) as prof:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while window < window_s:
                with jax.profiler.TraceAnnotation("bench.unit"):
                    chains.step(unit)
                    jax.block_until_ready(chains.params)
                units += 1
                window = time.perf_counter() - t0
    in_window = compiles.since_mark
    steps = units * unit
    tokens = mix["chains"] * mix["batch"] * mix["seq"] * steps
    note(f"window {window:.3f} s, {units} units of {unit} steps, {tokens} gradient tokens, "
         f"compiles in window {in_window}")
    device_line = harness.device_info(devices)
    if trace:
        reduced = prof.reduce(len(devices))
        ctx = {"kind": "sample", "cfg": cfg, "mix": mix, "trace": reduced, "window_s": window,
               "tokens": tokens, "steps": steps, "peak": harness.peak(devices[0].device_kind),
               "chips": len(devices), "flops": flops}
        out_metrics = harness.read_per_layer(cell["per_layer"], ctx)
        device_line.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    else:
        out_metrics = {"sample_tokens_per_s": {"value": tokens / window, "unit": "tokens/s"},
                       "setup_s": {"value": setup_s, "unit": "s"}}
    del chains
    gc.collect()
    t_ref = time.perf_counter()
    r = follow(cfg, mix, seed, mesh, seen)
    note(f"reference: {check_steps(mix)} steps in {time.perf_counter() - t_ref:.1f} s; "
         f"losses {r['loss']}")
    got = dict(readings(r), **structure)
    note(f"leaves left out of the change (reference gradient under {NOUGHT} of the median "
         f"leaf's): {got['left_out']}")
    limits = mix["check"]["limits"]
    checks = [(name, got[name], limits[name]) for name in checks_of(mix)]
    correct = all(v <= lim for _n, v, lim in checks)
    result = {"correct": bool(correct), "attempted": steps, "failed": 0,
              "metrics": out_metrics, "device": device_line}
    if trace:
        result["breakdown"] = reduced["breakdown"]
    return harness.finish(result, checks)
