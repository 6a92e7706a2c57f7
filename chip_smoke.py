#!/usr/bin/env python3
"""Chip smoke test: drive the system's main paths once on a TPU, at the
published widths of qwen3-0.6b (random weights from a seed), and check what
comes out.

  python chip_smoke.py             # one chip: serve, sample, fused sampler
  python chip_smoke.py --chips 4   # four chips: EC-SGHMC on a (chain,) mesh

One chip runs these phases, all in this one process:

* serve   — a K=2 bootstrap ensemble served by ``ServeEngine`` through the
  serve launcher (fused ``bma_select`` on, the TPU default): 8 greedy
  requests, prompts of 64 and 128 tokens, 16 new tokens each; then the same
  ensemble and trace through an engine with ``fused_select=False`` and a
  ``paged=True`` engine, whose tokens must equal the first engine's;
* sample  — the training launcher, one SGHMC chain, 10 steps of 4 x 512
  tokens: the NLL must be finite;
* fused   — fused EC-SGHMC (alpha=1, s=4) through ``ChainExecutor`` on a
  Gaussian target, with its noise drawn by the on-chip PRNG: the moments
  must fall inside the 3-sigma band of the exact discrete-time oracle.

``--chips 4`` runs only the (chain,) mesh path: EC-SGHMC with K=4 chains of
qwen3-0.6b, one per chip, through ``ChainExecutor.run_sharded`` (its
compiled program must hold exactly one all-reduce: the s-periodic
exchange), and as its comparison the toy Gaussian of
``benchmarks/shard_sweep.py`` on a 1-device and a 4-device mesh, whose
per-chain trajectories must be bit-identical.

Each check prints a line.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  Without a TPU the script exits
non-zero at once.  The compile cache is ``JAX_COMPILATION_CACHE_DIR`` where
set, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

ARCH = "qwen3-0.6b"


class SmokeFailure(RuntimeError):
    pass


_T0 = time.perf_counter()


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok [{time.perf_counter() - _T0:7.1f}s]: {what}", flush=True)


# --- one chip --------------------------------------------------------------


def _tokens_by_rid(report):
    return {r.rid: [int(t) for t in r.tokens] for r in report.results}


def _first_divergence(ref, rep) -> str:
    """Where two engines' token streams first part, with both engines'
    mixture log-probs there (the engines must record log-probs)."""
    import numpy as np

    a = {r.rid: r for r in ref.results}
    for r in rep.results:
        ta, tb = a[r.rid].tokens, r.tokens
        for i, (x, y) in enumerate(zip(ta, tb)):
            if x != y:
                la, lb = a[r.rid].logprobs[i], r.logprobs[i]
                top = np.argsort(-la)[:3]
                return (f"request {r.rid} token {i}: {x} vs {y}; logp[{x}] {la[x]:.6f} vs "
                        f"{lb[x]:.6f}, logp[{y}] {la[y]:.6f} vs {lb[y]:.6f}; top-3 {top.tolist()} "
                        f"max |dlogp| {float(np.max(np.abs(la - lb))):.3e}")
    return "no divergence"


def phase_serve(smoke: bool = False) -> None:
    """The serve launcher's engine path; then the same members and trace
    through a fused-select dense engine (which must reproduce the launcher),
    an unfused-select engine and a paged engine."""
    import numpy as np

    from repro import configs
    from repro.launch.serve import _bootstrap_ensemble
    from repro.launch.serve import main as serve_main
    from repro.models import get_model
    from repro.serve.engine import ServeEngine, SnapshotRegistry, synthetic_trace

    import jax

    prompt_len, gen, requests, slots, seed = 128, 16, 8, 4, 0
    argv = [
        "--arch", ARCH, "--engine", "--ensemble", "2", "--slots", str(slots),
        "--requests", str(requests), "--prompt-len", str(prompt_len),
        "--gen", str(gen), "--seed", str(seed),
    ]
    report = serve_main(argv + (["--smoke"] if smoke else []))
    cfg = configs.get_config(ARCH, smoke=smoke)
    toks = _tokens_by_rid(report)
    check(len(toks) == requests, f"{requests} requests served")
    check(all(len(t) == gen for t in toks.values()), f"every request returned {gen} tokens")
    flat = np.asarray([t for ts in toks.values() for t in ts])
    check(bool(np.all((flat >= 0) & (flat < cfg.vocab_size))), f"token ids in [0, {cfg.vocab_size})")
    check(report.trace_counts.get("decode") == 1, "one compiled decode program (fused select)")
    del report
    gc.collect()

    # the launcher's members and trace, rebuilt from the same seed
    model = get_model(cfg)
    members, _ = _bootstrap_ensemble(model.param_specs(cfg), jax.random.PRNGKey(seed), 2)
    trace = synthetic_trace(
        requests, vocab_size=cfg.vocab_size, prompt_lens=(prompt_len // 2, prompt_len),
        max_new=gen, mean_interarrival=2.0, seed=seed,
    )
    max_seq = prompt_len + gen + 1
    reports = {}
    for label, kw in (("fused dense", {}), ("fused_select=False", {"fused_select": False}),
                      ("paged=True", {"paged": True})):
        engine = ServeEngine(
            cfg, model, SnapshotRegistry(members), num_slots=slots, max_seq=max_seq,
            seed=seed, record_logprobs=True, **kw,
        )
        rep = reports[label] = engine.run(trace)
        del engine
        gc.collect()
        check(rep.trace_counts.get("decode") == 1, f"one compiled decode program ({label})")
        if label != "fused dense":
            print(f"  {label} vs fused dense: {_first_divergence(reports['fused dense'], rep)}",
                  flush=True)
        check(_tokens_by_rid(rep) == toks, f"{label} tokens equal the launcher's tokens")


def phase_sample(smoke: bool = False) -> None:
    """The training launcher: one SGHMC chain over the model's posterior."""
    from repro.launch.train import main as train_main

    history = train_main(
        ["--arch", ARCH, "--chains", "1", "--steps", "10", "--batch", "4", "--seq", "512"]
        + (["--smoke"] if smoke else [])
    )
    check(len(history) == 1, "one logged chunk of 10 steps")
    nll = history[-1]["nll_per_token"]
    check(math.isfinite(nll) and nll > 0.0, f"finite NLL per token ({nll:.4f})")


EC_KW = dict(friction=1.0, center_friction=1.0, noise_convention="eq6", center_noise_in_p=False)
MU, LAM = 1.5, 1.0  # Gaussian target N(MU, 1/LAM) per dimension


def phase_fused(steps: int = 30_000) -> None:
    """Fused EC-SGHMC on a Gaussian target vs the exact discrete-time
    oracle, as the stationary battery gates it, with on-chip noise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import core
    from repro import diagnostics as diag
    from repro.kernels import fused_ec_update
    from repro.kernels.ops import _noise_kwargs, _on_tpu
    from repro.run import rollout

    if _on_tpu():
        check(set(_noise_kwargs(jax.random.PRNGKey(0), (8, 1024))) == {"seed"},
              "fused kernel draws its noise on chip from the caller's key")

    # the kernel's noise law over many blocks: standard normal, different
    # per key, uncorrelated across blocks
    zeros = jnp.zeros((64, 8192), jnp.float32)
    hyper = dict(eps=0.0, friction=0.0, mass=1.0, alpha=0.0, sigma_p=1.0, stochastic_round=False)
    _, n1 = fused_ec_update(zeros, zeros, zeros, zeros, jax.random.PRNGKey(1), **hyper)
    _, n2 = fused_ec_update(zeros, zeros, zeros, zeros, jax.random.PRNGKey(2), **hyper)
    n1, n2 = np.asarray(n1, np.float64), np.asarray(n2, np.float64)
    check(abs(n1.mean()) < 0.01 and abs(n1.std() - 1.0) < 0.01,
          f"kernel noise ~ N(0,1) (mean {n1.mean():+.4f}, std {n1.std():.4f})")
    blocks = n1.reshape(-1, 8192)
    corr = max(abs(np.corrcoef(blocks[0], blocks[i])[0, 1]) for i in (1, 2, 63))
    key_corr = abs(np.corrcoef(n1.ravel(), n2.ravel())[0, 1])
    check(corr < 0.06 and key_corr < 0.01,
          f"blocks and keys draw independent noise (|corr| {corr:.4f}, {key_corr:.4f})")

    K, D, s, eps = 4, 2, 4, 0.1
    sampler = core.ec_sghmc(step_size=eps, alpha=1.0, sync_every=s, fused=True, **EC_KW)
    keys = jax.random.split(jax.random.PRNGKey(7), steps)
    res = rollout(
        sampler, lambda th: LAM * (th - MU), jnp.full((K, D), MU + 1.0, jnp.float32),
        num_steps=steps, keys=keys, chunk_steps=8192,
    )
    traj = np.moveaxis(np.asarray(res.trace), 1, 0)[:, 4_000:]  # (K, T, D)
    oracle = diag.ec_sghmc_stationary(
        step_size=eps, alpha=1.0, num_chains=K, sync_every=s, precision=LAM, mu=MU, **EC_KW,
    )
    mean, var = diag.pooled_moments(traj)
    ess = float(np.sum(diag.coupled_ess_nd(traj)))
    mean_tol = 3.0 * math.sqrt(oracle.theta_var / ess) + 1e-4
    var_tol = diag.monte_carlo_tolerance(oracle.theta_var, ess) + 1e-6
    check(abs(mean.mean() - oracle.theta_mean) < mean_tol,
          f"mean {mean.mean():.5f} within 3 sigma of oracle {oracle.theta_mean} (tol {mean_tol:.5f})")
    check(abs(var.mean() - oracle.theta_var) < var_tol,
          f"var {var.mean():.5f} within 3 sigma of oracle {oracle.theta_var:.5f} (tol {var_tol:.5f})")


# --- four chips --------------------------------------------------------------


_OTHER_COLLECTIVES = ("all-gather", "all-to-all", "collective-permute", "reduce-scatter")


def collectives(hlo_text: str) -> dict:
    """Collective instructions in compiled HLO text (an async start/done
    pair counts once): all-reduces, how many of them JAX placed in the sync
    branch of the s-periodic ``lax.cond``, and every other collective."""
    ops = [ln for ln in hlo_text.splitlines() if " = " in ln]
    is_op = lambda ln, op: f" {op}(" in ln or f" {op}-start(" in ln
    reduces = [ln for ln in ops if is_op(ln, "all-reduce")]
    return {
        "all_reduce": len(reduces),
        "in_sync_branch": sum("/cond/branch_1_fun/" in ln for ln in reduces),
        "other": sum(is_op(ln, op) for ln in ops for op in _OTHER_COLLECTIVES),
    }


def phase_lm_mesh(num_devices: int = 4, smoke: bool = False) -> None:
    """K chains of the model, one per chip, through ``run_sharded``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs, core
    from repro.core import tree_broadcast_axis0
    from repro.launch.mesh import make_chain_mesh
    from repro.models import get_model, init_params
    from repro.run import ChainExecutor
    from repro.train.step import make_grad_fn

    cfg = configs.get_config(ARCH, smoke=smoke)
    model = get_model(cfg)
    K, steps, sync, batch, seq = num_devices, 8, 4, 4, 512
    # f32 sampler state does not fit a 16 GiB chip at these widths; bf16 does
    sampler = core.ec_sghmc(step_size=1e-6, alpha=1.0, sync_every=sync, chain_axis="chain",
                            state_dtype=jnp.bfloat16)
    data_key = jax.random.PRNGKey(11)

    def device_batch_fn(t):
        # one chain per shard inside shard_map: a (1, batch, seq) token batch
        toks = jax.random.randint(jax.random.fold_in(data_key, t), (1, batch, seq + 1),
                                  0, cfg.vocab_size, jnp.int32)
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}

    ex = ChainExecutor(sampler=sampler, grad_fn=make_grad_fn(cfg, model, n_data=100_000),
                       device_batch_fn=device_batch_fn, chunk_steps=steps, key_mode="fold")
    mesh = make_chain_mesh(num_devices)
    params1 = init_params(model.param_specs(cfg), jax.random.PRNGKey(0))
    chain_sharded = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("chain"))
    # each chip materializes only its own chain
    params = jax.jit(lambda p: tree_broadcast_axis0(p, K), out_shardings=chain_sharded)(params1)
    state = sampler.init(params)
    corner0 = np.asarray(params1["embed"]["table"][:8, :8], np.float32)
    del params1  # the chips' memory is the limit here
    coll = collectives(ex.lower_sharded(params, state, num_steps=steps, key=jax.random.key(0),
                                        mesh=mesh).compile().as_text())
    print(f"  compiled chunk collectives: {coll}", flush=True)
    check(coll["all_reduce"] >= 1 and coll["in_sync_branch"] == coll["all_reduce"]
          and coll["other"] == 0,
          f"the s-periodic exchange is the program's only collective "
          f"({coll['all_reduce']} all-reduce instructions, all in the sync branch)")
    res = ex.run_sharded(params, state, num_steps=steps, key=jax.random.key(0), mesh=mesh)
    leaves = jax.tree.leaves(res.params) + jax.tree.leaves(res.state.center)
    check(all(bool(jnp.all(jnp.isfinite(x))) for x in leaves), "chains and center finite")
    corner = np.asarray(res.params["embed"]["table"][:, :8, :8], np.float32)  # (K, 8, 8)
    moved = float(np.max(np.abs(corner - corner0)))
    check(moved > 0.0, f"chains moved ({steps} steps, max |d embed| {moved:.3e})")
    check(not np.all(corner == corner[0]), "per-chain noise differs across chips")


def phase_toy_mesh(num_devices: int = 4) -> None:
    """``benchmarks/shard_sweep.py``'s Gaussian on a 1-device and a
    ``num_devices`` mesh: per-chain trajectories bit-identical at alpha=0."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import core
    from repro.launch.mesh import make_chain_mesh
    from repro.run import ChainExecutor

    K, D, steps, sync = 4, 262_144, 256, 4
    mu = jnp.zeros((D,), jnp.float32)
    params0 = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (K, D), jnp.float32)
    sampler = core.ec_sghmc(step_size=1e-3, alpha=0.0, sync_every=sync,
                            noise_convention="eq6", chain_axis="chain", per_chain_noise=True)
    out = {}
    for n in (1, num_devices):
        ex = ChainExecutor(sampler=sampler, grad_fn=lambda t, _b: t - mu,
                           chunk_steps=steps, key_mode="fold")
        mesh = make_chain_mesh(n)
        if n == num_devices:
            coll = collectives(ex.lower_sharded(
                params0 + 0.0, sampler.init(params0), num_steps=steps, key=jax.random.key(0),
                mesh=mesh).compile().as_text())
            print(f"  {n}-device chunk collectives: {coll}", flush=True)
            check(coll == {"all_reduce": 1, "in_sync_branch": 1, "other": 0},
                  f"{n}-device chunk holds exactly one all-reduce, in the sync branch")
        res = ex.run_sharded(params0 + 0.0, sampler.init(params0), num_steps=steps,
                             key=jax.random.key(0), mesh=mesh)
        out[n] = np.asarray(res.params)
    check(bool(np.all(np.isfinite(out[1]))), "toy chains finite")
    diff = float(np.max(np.abs(out[num_devices] - out[1])))
    check(np.array_equal(out[num_devices], out[1]),
          f"per-chain trajectories bit-identical on 1 and {num_devices} devices (max diff {diff})")


# --- entry point --------------------------------------------------------------


class CompileLog:
    """Seconds JAX spent compiling or loading programs from the persistent
    cache (``backend_compile_duration`` wraps both), and the cache hits."""

    def __init__(self):
        import jax

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __str__(self):
        return (f"{self.seconds:.1f}s compiling {self.programs} programs "
                f"({self.cache_hits} persistent-cache hits)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve/sample/fused phases; 4: the (chain,) mesh path only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips; found {len(devices)}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache {enable_compile_cache()}",
          flush=True)
    if args.chips == 1:
        phases = [("serve", phase_serve), ("sample", phase_sample), ("fused", phase_fused)]
    else:
        phases = [("lm_mesh", lambda: phase_lm_mesh(args.chips)),
                  ("toy_mesh", lambda: phase_toy_mesh(args.chips))]
    compiles = CompileLog()
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        print(f"phase {name}", flush=True)
        fn()
        gc.collect()
        print(f"PASS {name} {time.perf_counter() - t0:.1f}s; so far {compiles}", flush=True)
    print(f"all phases passed in {time.perf_counter() - t_all:.1f}s; {compiles}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
