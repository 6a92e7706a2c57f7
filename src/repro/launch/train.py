"""Training launcher: EC-SGHMC posterior sampling over any assigned arch.

The step loop is device-resident (``repro.run.ChainExecutor`` via
``train.loop``): whole chunks of sampler steps compile as one scan program,
and the sampler's jit-safe ``stats`` hook is logged at chunk boundaries.

CPU-runnable end-to-end with --smoke (reduced config); the production mesh
path is exercised by dryrun.py.  Example:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 100 --chains 4 --sync-every 4 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro import configs
from repro import obs
from repro.launch.compile_cache import enable_compile_cache
from repro.core import tree_broadcast_axis0
from repro.data import synthetic_token_stream
from repro.data.pipeline import chain_batches
from repro.launch.specs import default_sampler, vlm_patches
from repro.models import get_model, init_params
from repro.train.loop import LoopConfig, run
from repro.train.step import make_train_step

log = obs.get_logger("train")


def build_batch_fn(cfg, num_chains: int, per_chain: int, seq_len: int, seed: int = 0):
    sampler = synthetic_token_stream(cfg.vocab_size, seed)

    def fn(step: int):
        batch = chain_batches(sampler, step, num_chains, per_chain, seq_len)
        if cfg.family == "audio":
            key = jax.random.fold_in(jax.random.PRNGKey(seed + 7), step)
            batch["frame_embeds"] = 0.02 * jax.random.normal(
                key, (num_chains, per_chain, cfg.enc_seq, cfg.d_model), jnp.float32
            ).astype(cfg.compute_dtype)
        if cfg.family == "vlm":
            key = jax.random.fold_in(jax.random.PRNGKey(seed + 8), step)
            n_patch = vlm_patches(seq_len)
            n_text = seq_len - n_patch
            batch["tokens"] = batch["tokens"][..., :n_text]
            batch["labels"] = batch["labels"][..., :n_text]
            batch["patch_embeds"] = 0.02 * jax.random.normal(
                key, (num_chains, per_chain, n_patch, cfg.d_model), jnp.float32
            ).astype(cfg.compute_dtype)
        return batch

    return fn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--chains", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4, help="per-chain batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument("--step-size", type=float, default=1e-6)
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--n-data", type=float, default=100_000,
                    help="corpus size for the N/|B| potential scale")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--preempt-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Perfetto trace.json of the run to PATH")
    args = ap.parse_args(argv)

    tracer, trace_path = obs.configure(args.trace)
    log.info(f"compile cache: {enable_compile_cache()}")
    cfg = configs.get_config(args.arch, smoke=args.smoke)
    model = get_model(cfg)
    sampler = default_sampler(cfg, args.arch, args.chains, args.sync_every)
    # override the conservative default step size
    from repro.core import ec_sghmc, sghmc

    if args.chains > 1:
        sampler = ec_sghmc(
            step_size=args.step_size, alpha=args.alpha, sync_every=args.sync_every,
            state_dtype=cfg.param_dtype,
        )
    else:
        sampler = sghmc(step_size=args.step_size, state_dtype=cfg.param_dtype)

    train_step = make_train_step(cfg, model, sampler, n_data=int(args.n_data))
    params1 = init_params(model.param_specs(cfg), jax.random.PRNGKey(args.seed))
    params = tree_broadcast_axis0(params1, args.chains)
    state = sampler.init(params)
    batch_fn = build_batch_fn(cfg, args.chains, args.batch, args.seq, args.seed)

    loop_cfg = LoopConfig(
        num_steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        preempt_at=args.preempt_at,
        seed=args.seed,
    )
    params, state, history = run(
        train_step, params, state, batch_fn, loop_cfg,
        num_chains=args.chains, alpha=args.alpha, sampler=sampler,
    )
    if history:
        log.info(f"final nll/token: {history[-1]['nll_per_token']:.4f}")
    if trace_path:
        tracer.export(trace_path)
        log.info(f"trace written to {trace_path} ({len(tracer)} events)")
    return history


if __name__ == "__main__":
    main()
