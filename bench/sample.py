"""A sampling cell: one SGHMC chain over the model's posterior on one chip,
through ``ChainExecutor.run``.

Set-up makes the chain from the seed, builds the executor and its state,
and drives that same object through its first three steps, one call of the
window's own kind per step, keeping what the check reads: the momentum
after step 1, the positions after step 3 and each step's loss.  The window
then runs whole units of ``unit_steps`` steps, each ended by
``block_until_ready``.  Afterwards the float32 reference follows the same
steps and the two are compared, each number against the limit the mix's
``check`` block gives it.
"""
from __future__ import annotations

import gc
import time

import numpy as np

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
FIRST_STEPS = 3  # driven through the window's call, then followed by the reference
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


def model_config(cfg: dict):
    import serve

    return serve.model_config(cfg).replace(remat=cfg["deployment"]["remat"])


def make_sampler(dep: dict, mix: dict):
    import jax.numpy as jnp

    from repro import core

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

        from repro.models import get_model
        from repro.run import ChainExecutor
        from repro.train.step import make_grad_fn

        dep = cfg["deployment"]
        mcfg = model_config(cfg)
        model = get_model(mcfg)
        weights.check_layout(cfg, model.param_specs(mcfg))
        self.K = mix["chains"]
        data_key = jax.random.key(DATA_KEY_STREAM)
        self.key = harness.seed_key(seed, 3)
        B, S, V = mix["batch"], mix["seq"], cfg["vocab_size"]

        def batch_fn(t):
            return jax.vmap(lambda c: traffic.token_batch(data_key, t, c, B, S, V))(
                jax.numpy.arange(self.K))

        self.sampler = make_sampler(dep, mix)
        self.ex = ChainExecutor(
            sampler=self.sampler,
            grad_fn=make_grad_fn(mcfg, model, n_data=dep["n_data"], weight_decay=dep["weight_decay"]),
            device_batch_fn=batch_fn, chunk_steps=1, key_mode="fold")
        self.params = weights.make(cfg, seed, self.K, out_sharding=NamedSharding(mesh, P("chain")))
        self.state = self.sampler.init(self.params)
        self.t = start_step(seed)

    def step(self, n: int):
        """Advance ``n`` steps in one call; returns the loss the program
        reports for the last of them."""
        res = self.ex.run(self.params, self.state, num_steps=n, key=self.key, start_step=self.t)
        self.params, self.state, self.t = res.params, res.state, self.t + n
        return res.metrics.get("nll_per_token")


def to_host(tree):
    import jax

    return jax.tree.map(np.asarray, jax.device_get(tree))


def first_steps(chains: Chains) -> dict:
    """The first steps through the window's call, and what the check reads."""
    seen = {"loss": []}
    for i in range(FIRST_STEPS):
        seen["loss"].append(float(chains.step(1)))
        if i == 0:
            seen["p1"] = to_host(chains.state.momentum)
        if i == 2:
            seen["theta3"] = to_host(chains.params)
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


def follow(cfg: dict, mix: dict, seed: int, mesh, seen: dict | None, precision: str = "f32",
           fault: str | None = None, record: bool = False) -> dict:
    """The reference over the first three steps on the cell's mesh, and the
    per-leaf norms it reads of itself and of ``seen`` (what the system
    under test kept).  With ``precision``/``fault`` it is instead the
    control or a planted fault, and ``record`` keeps what it produced in
    the shape of ``seen``, to be put in the program's place.  ``fault``:
    "unchanged" (the step returns its state), "half_batch" (the loss over
    the first half of the rows)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    ref = harness.reference(cfg)
    dep = cfg["deployment"]
    sd = jnp.dtype(dep["state_dtype"])
    data_key, run_key = jax.random.key(DATA_KEY_STREAM), harness.seed_key(seed, 3)
    t0 = start_step(seed)
    B, S, V = mix["batch"], mix["seq"], cfg["vocab_size"]
    eps = dep["step_size"]
    sigma = eps * np.sqrt(2.0 * dep["friction"])
    chain = NamedSharding(mesh, P("chain"))
    rows = B // 2 if fault == "half_batch" else None

    def smap(fn, in_specs, out_specs):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                                 check_vma=False))

    sq = lambda tree: jax.tree.map(lambda x: x[0], tree)
    ex = lambda tree: jax.tree.map(lambda x: x[None], tree)
    norms = lambda tree: jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                                    for x in jax.tree.leaves(tree)])[None]

    def grad_fn(theta, t):
        i = jax.lax.axis_index("chain")
        batch = traffic.token_batch(data_key, t, i, B, S, V)
        _, loss, g = ref.potential_and_grad(cfg, sq(theta), batch, n_data=dep["n_data"],
                                            weight_decay=dep["weight_decay"],
                                            precision=precision, rows=rows)
        return ex(g), jax.lax.pmean(loss, "chain")

    grad = smap(grad_fn, (P("chain"), P()), (P("chain"), P()))

    def noise_of(p, t, key):
        """The chain's standard normal draw at step t, by the deployment's
        key convention (``key`` is the run's key): one draw over the
        (1, ...) leaves."""
        return sgmcmc.tree_normal(jax.random.fold_in(key, t), p)

    def step_fn(theta, p, g, t, key):
        if fault == "unchanged":
            return theta, p
        return sgmcmc.sghmc_step(dep, theta, p, g, noise_of(p, t, key))

    step = jax.jit(shard_map(step_fn, mesh=mesh, in_specs=(P("chain"),) * 3 + (P(), P()),
                             out_specs=(P("chain"), P("chain")), check_vma=False),
                   donate_argnums=(0, 1, 2))

    def recon_fn(p1, t, key):
        """The first gradient, worked out from the momentum after step 1
        (the momentum starts at zero)."""
        n0 = noise_of(p1, t, key)
        return norms(jax.tree.map(lambda m, n: -(m.astype(jnp.float32) - sigma * n) / eps,
                                  p1, n0))

    recon = smap(recon_fn, (P("chain"), P(), P()), P("chain"))
    change = smap(lambda a, b: norms(jax.tree.map(lambda x, y: x - y, a, b)),
                  (P("chain"), P("chain")), P("chain"))
    leafnorms = smap(norms, P("chain"), P("chain"))

    theta0 = weights.make(cfg, seed, mix["chains"], out_sharding=chain)
    theta = jax.tree.map(jnp.copy, theta0)
    p = jax.tree.map(lambda x: jnp.zeros(x.shape, sd, device=chain), theta0)
    out = {"loss": [], "seen_loss": seen["loss"][:FIRST_STEPS] if seen else []}
    own = {"loss": []}
    for t in range(FIRST_STEPS):
        g, loss = grad(theta, jnp.int32(t0 + t))
        out["loss"].append(float(loss))
        own["loss"].append(float(loss))
        if t == 0:
            out["grad0"] = np.asarray(leafnorms(g))
        theta, p = step(theta, p, g, jnp.int32(t0 + t), run_key)
        if t == 0:
            t00 = jnp.int32(t0)
            out["grad_ref"] = np.asarray(recon(p, t00, run_key))
            if record:
                own["p1"] = to_host(p)
            if seen:
                out["grad_prog"] = np.asarray(recon(jax.device_put(seen["p1"], chain), t00,
                                                    run_key))
        if t == 2:
            out["change_ref"] = np.asarray(change(theta, theta0))
            if record:
                own["theta3"] = to_host(theta)
            if seen:
                out["change_prog"] = np.asarray(change(jax.device_put(seen["theta3"], chain),
                                                       theta0))
    if record:
        out["own"] = own
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
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, devices, t_start: float,
        compiles) -> dict:
    import jax
    from jax.sharding import Mesh

    cfg, mix = cell["cfg"], cell["mix"]
    if mix["chains"] != cell["chips"]:
        raise harness.CellError("a sampling cell runs one chain per chip")
    mesh = Mesh(np.asarray(devices), ("chain",))
    chains = Chains(cfg, mix, seed, mesh)
    seen = first_steps(chains)
    warm_unit(chains, mix["unit_steps"])
    window_s = min(seconds, harness.TRACE_SECONDS) if trace else seconds
    note(f"first {FIRST_STEPS} steps done, losses {seen['loss']}; {compiles}")
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
    note(f"reference: {FIRST_STEPS} steps in {time.perf_counter() - t_ref:.1f} s; "
         f"losses {r['loss']}")
    got = readings(r)
    note(f"leaves left out of the change (reference gradient under {NOUGHT} of the median "
         f"leaf's): {got['left_out']}")
    limits = mix["check"]["limits"]
    checks = [(name, got[name], limits[name]) for name in CHECKS]
    correct = all(v <= lim for _n, v, lim in checks)
    result = {"correct": bool(correct), "attempted": steps, "failed": 0,
              "metrics": out_metrics, "device": device_line}
    if trace:
        result["breakdown"] = reduced["breakdown"]
    return harness.finish(result, checks)
