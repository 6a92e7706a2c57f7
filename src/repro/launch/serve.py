"""Serving launcher.

Two paths:

* legacy single-stream decoding (+ ``ensemble_decode``, the vmapped
  whole-batch Bayesian-model-averaging loop — kept as the simple reference
  implementation);
* ``--engine``: the continuous-batching posterior-predictive engine
  (``repro.serve.engine``) — request-level scheduling over a fixed slot
  axis, cache pooling, BMA over K ensemble members, and (``--refresh-every``)
  live snapshot refresh from a background coupled-sampler run.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --batch 4 --prompt-len 16 --gen 8 --ensemble 2
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --engine --slots 4 --requests 12 --ensemble 2 --refresh-every 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro import core
from repro import obs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model, init_params
from repro.serve.engine import (
    ChainRefresher,
    RefreshScheduler,
    ServeEngine,
    SnapshotRegistry,
    synthetic_trace,
)
from repro.serve.loop import (
    collect_ensemble,
    ensemble_diagnostics,
    make_decode_step,
    make_prefill_step,
)
from repro.serve.sampling import SamplingParams

log = obs.get_logger("serve")

# prior-bootstrap ensemble: members are thinned SGLD draws from
# N(params_init, PRIOR_SCALE^2 I) — a posterior stand-in when no sampled
# checkpoint is supplied; the spread matches the init scale so BMA is
# exercised with realistic dispersion.
PRIOR_SCALE = 0.02
_PREC = 1.0 / PRIOR_SCALE**2
_EPS = 0.2 / _PREC  # eps*lam = 0.2: stable, mixes in ~5 steps


def _prior_grad(center):
    """grad of the bootstrap prior N(center, PRIOR_SCALE^2 I); leaf
    broadcasting makes it work for unstacked and (K,...)-stacked params."""
    return lambda p: jax.tree.map(lambda x, x0: _PREC * (x - x0), p, center)


def _bootstrap_ensemble(specs, key, num: int):
    """Members = centre + offsets, the offsets sampled from N(0, PRIOR_SCALE^2 I).
    Sampling the offset keeps the centre out of the compiled sampler: a
    closed-over centre would be embedded in the program as a constant the
    size of the model."""
    center = init_params(specs, key)
    offsets, res = collect_ensemble(
        core.sgld(step_size=_EPS),
        lambda d: jax.tree.map(lambda x: _PREC * x, d),
        jax.tree.map(jnp.zeros_like, center),
        num_samples=num, key=jax.random.fold_in(key, 1), thin=16,
    )
    members = jax.tree.map(lambda c, d: c[None] + d, center, offsets)
    return members, res


def _live_refresher(specs, key, registry: SnapshotRegistry, chunk_steps: int = 16,
                    mode: str = "overlapped"):
    """Background chain-stacked SGLD over the same bootstrap prior — the
    live run whose chunk-boundary chain stack refreshes the registry.
    ``mode='overlapped'`` (default) builds the async ``RefreshScheduler``
    (DESIGN.md §9); ``'sync'`` keeps the legacy inline ``ChainRefresher``."""
    center = init_params(specs, key)
    start = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (registry.num_members,) + x.shape) + 0.0, center
    )
    cls = RefreshScheduler if mode == "overlapped" else ChainRefresher
    return cls(
        registry,
        core.sgld(step_size=_EPS),
        _prior_grad(center),
        start,
        key=jax.random.fold_in(key, 2),
        chunk_steps=chunk_steps,
    )


def ensemble_decode(cfg, model, params_stack, batch, max_seq: int, num_tokens: int):
    """Average predictive probs over the chain/ensemble axis of params."""
    k = jax.tree.leaves(params_stack)[0].shape[0]

    def prefill_one(p):
        return model.prefill(cfg, p, batch, max_seq)

    logits, caches = jax.vmap(prefill_one)(params_stack)
    probs = jnp.mean(jax.nn.softmax(logits.astype(jnp.float32), -1), axis=0)
    tok = jnp.argmax(probs[:, -1], -1).astype(jnp.int32)[:, None]
    out = [tok]

    def step_one(p, c, t):
        return model.decode_step(cfg, p, c, t)

    vstep = jax.jit(jax.vmap(step_one, in_axes=(0, 0, None)))
    for _ in range(num_tokens - 1):
        logits, caches = vstep(params_stack, caches, tok)
        probs = jnp.mean(jax.nn.softmax(logits.astype(jnp.float32), -1), axis=0)
        tok = jnp.argmax(probs[:, -1], -1).astype(jnp.int32)[:, None]
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def _run_engine(args, cfg, model):
    specs = model.param_specs(cfg)
    key = jax.random.PRNGKey(args.seed)
    k = max(args.ensemble, 1)
    if k > 1:
        members, res = _bootstrap_ensemble(specs, key, k)
        log.info(f"ensemble: K={k} collected at {res.steps_per_s:.0f} steps/s")
    else:
        members = jax.tree.map(lambda x: x[None], init_params(specs, key))
    registry = SnapshotRegistry(members)
    refresher = None
    if args.refresh_every and k > 1:
        refresher = _live_refresher(specs, key, registry, mode=args.refresh_mode)
    max_seq = args.prompt_len + args.gen + 1
    engine = ServeEngine(
        cfg, model, registry,
        num_slots=args.slots, max_seq=max_seq,
        sampling=SamplingParams(args.temperature, args.top_k),
        bma=args.bma, eos_id=args.eos, seed=args.seed,
        refresher=refresher, refresh_every=args.refresh_every,
    )
    trace = synthetic_trace(
        args.requests,
        vocab_size=cfg.vocab_size,
        prompt_lens=(max(args.prompt_len // 2, 1), args.prompt_len),
        max_new=args.gen,
        mean_interarrival=args.interarrival,
        seed=args.seed,
    )
    report = engine.run(trace)
    pct = report.latency_percentiles()
    log.info(
        f"served {len(report.results)} requests / {report.total_tokens} tokens "
        f"in {report.wall_s:.2f}s ({report.tokens_per_s:.1f} tok/s, "
        f"slots={args.slots}, K={k}, decode_traces={report.trace_counts.get('decode')})"
    )
    log.info(
        f"latency p50={pct['latency_p50_s'] * 1e3:.1f}ms p99={pct['latency_p99_s'] * 1e3:.1f}ms  "
        f"first-token p50={pct['first_token_p50_s'] * 1e3:.1f}ms "
        f"p99={pct['first_token_p99_s'] * 1e3:.1f}ms"
    )
    if refresher is not None:
        rf = report.refresher
        log.info(f"snapshots: {report.registry['version']} promoted, {report.registry['rejected']} rejected, "
              f"{rf['steps_done']} sampler steps")
        if "pump_wall_s" in rf:  # overlapped scheduler observability
            log.info(
                f"overlap: {rf['micro_chunks']} micro-chunks of {rf['micro_steps']} steps "
                f"on {rf['device'] or 'default device'}, pump {rf['pump_wall_s']:.3f}s, "
                f"per-refresh {rf['per_refresh_wall_s'] * 1e3:.1f}ms, "
                f"stalled {rf['decode_steps_stalled']} ticks ({rf['stall_wall_s']:.3f}s), "
                f"deferred {rf['flips_deferred']} flips"
            )
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--ensemble", type=int, default=1, help="posterior samples to average")
    ap.add_argument("--seed", type=int, default=0)
    # engine path
    ap.add_argument("--engine", action="store_true", help="continuous-batching engine")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--interarrival", type=float, default=2.0, help="mean decode-steps between arrivals")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--bma", choices=("probs", "logprobs"), default="probs")
    ap.add_argument("--eos", type=int, default=None)
    ap.add_argument("--refresh-every", type=int, default=0,
                    help="decode-step cadence of live snapshot refresh (0 = frozen members)")
    ap.add_argument("--refresh-mode", choices=("overlapped", "sync"), default="overlapped",
                    help="overlapped: async micro-chunk scheduler (decode never stalls); "
                         "sync: legacy inline ChainRefresher")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Perfetto trace.json of the run to PATH")
    args = ap.parse_args(argv)

    tracer, trace_path = obs.configure(args.trace)
    log.info(f"compile cache: {enable_compile_cache()}")
    cfg = configs.get_config(args.arch, smoke=args.smoke)
    model = get_model(cfg)
    if args.engine:
        report = _run_engine(args, cfg, model)
        if trace_path:
            tracer.export(trace_path)
            log.info(f"trace written to {trace_path} ({len(tracer)} events)")
        return report
    max_seq = args.prompt_len + args.gen + 1
    key = jax.random.PRNGKey(args.seed)
    batch = {"tokens": jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    if cfg.family == "audio":
        batch["frame_embeds"] = 0.02 * jax.random.normal(key, (args.batch, cfg.enc_seq, cfg.d_model))

    t0 = time.time()
    if args.ensemble > 1:
        # device-resident collection: one compiled sampler run, thinned
        # trace = the ensemble (repro.serve.loop.collect_ensemble)
        params, res = _bootstrap_ensemble(
            model.param_specs(cfg), jax.random.PRNGKey(args.seed), args.ensemble
        )
        health = ensemble_diagnostics(params)
        log.info(
            f"ensemble: K={health['num_chains']} spread={health['chain_spread']:.3e} "
            f"rel={health['rel_spread']:.3e} "
            f"(collected at {res.steps_per_s:.0f} steps/s)"
            + (" [COLLAPSED — BMA is a no-op]" if health["collapsed"] else "")
        )
        toks = ensemble_decode(cfg, model, params, batch, max_seq, args.gen)
    else:
        params = init_params(model.param_specs(cfg), key)
        prefill = jax.jit(make_prefill_step(cfg, model, max_seq))
        step = jax.jit(make_decode_step(cfg, model))
        tok, cache = prefill(params, batch)
        out = [tok]
        for _ in range(args.gen - 1):
            tok, cache = step(params, cache, tok)
            out.append(tok)
        toks = jnp.concatenate(out, axis=1)
    dt = time.time() - t0
    log.info(f"generated {toks.shape} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s, ensemble={args.ensemble})")
    log.info(str(toks))
    if trace_path:
        tracer.export(trace_path)
        log.info(f"trace written to {trace_path} ({len(tracer)} events)")
    return toks


if __name__ == "__main__":
    main()
