"""Serving-engine latency/throughput bench (``BENCH_serve.json``).

Drives the continuous-batching posterior-predictive engine
(``repro.serve.engine``) with open-loop synthetic request traces on the
smoke-sized qwen3 config and records, per (slots, K, offered-load)
configuration: p50/p99 request latency, p50/p99 first-token latency, and
aggregate tokens/s — the serving tier's perf trajectory across PRs.  One
configuration additionally runs with live snapshot refresh enabled to price
the refresh cost in-band, and a dense-vs-paged sweep (with and without
prefix sharing, on a prompt-pool trace) records the DESIGN.md §8 memory
axes: KV bytes per request (high-water for paged, static footprint for
dense) and the prefix-cache hit rate.

The ``refresh_slo`` variant is the DESIGN.md §9 acceptance row: on a
compile-warmed engine pair it compares *continuous* overlapped background
refresh against the frozen-ensemble baseline and records the p99 ratio and
tokens/s under refresh (targets: p99 <= 1.2x frozen, tok/s >= 2x the old
synchronous-refresh row).  Both engines serve a tiny warm-up trace first so
the ratio prices refresh, not first-call compilation.  The pair runs in a
forced-2-host-device SUBPROCESS (``repro.launch.mesh.forced_device_env``,
the same fallback the shard sweep uses) so the scheduler has a spare device
to park the background sampler on — on the parent's already-locked
single-device backend the sampler would serialize with decode and the row
would measure queueing, not overlap.

CSV rows keep the historical ``name,us_per_call,derived`` shape:
us_per_call = mean decode-step wall time, derived = tokens/s.
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np

from repro import configs
from repro.models import get_model, init_params
from repro.launch.mesh import forced_device_env
from repro.launch.serve import _live_refresher
from repro.serve.engine import Request, ServeEngine, SnapshotRegistry, synthetic_trace

from common import QUICK, emit, record

ARCH = "qwen3-0.6b"
# (slots, K, mean_interarrival decode-steps): two slot widths x two ensemble
# sizes, light and heavy offered load on the wider one
GRID_QUICK = [
    (2, 1, 2.0),
    (4, 2, 2.0),
    (4, 2, 0.5),
]
GRID_FULL = GRID_QUICK + [
    (8, 4, 2.0),
    (8, 4, 0.5),
]


def _members(cfg, model, k: int, seed: int = 0):
    keys = jax.random.split(jax.random.PRNGKey(seed), k)
    return jax.vmap(lambda kk: init_params(model.param_specs(cfg), kk))(keys)


PROMPT_LENS = (8, 16)


def _one_config(cfg, model, slots, k, interarrival, *, num_requests, max_new,
                refresh=False, refresh_mode="sync", refresh_chunk=16,
                refresh_every=8, warm=False, prompt_pool=0, **engine_kw):
    registry = SnapshotRegistry(_members(cfg, model, k))
    refresher = None
    if refresh:
        refresher = _live_refresher(
            model.param_specs(cfg), jax.random.PRNGKey(7), registry,
            chunk_steps=refresh_chunk, mode=refresh_mode,
        )
    engine = ServeEngine(
        cfg, model, registry,
        num_slots=slots, max_seq=max(PROMPT_LENS) + max_new,
        refresher=refresher, refresh_every=refresh_every if refresh else 0,
        **engine_kw,
    )
    if warm:
        # compile admit (both prompt lengths) + decode off the clock, so the
        # timed report prices steady-state serving, not first-call tracing
        engine.run([
            Request(rid=9000 + i, prompt=np.arange(1, L + 1, dtype=np.int32), max_new=2)
            for i, L in enumerate(PROMPT_LENS)
        ])
    trace = synthetic_trace(
        num_requests,
        vocab_size=cfg.vocab_size,
        prompt_lens=PROMPT_LENS,
        max_new=max_new,
        mean_interarrival=interarrival,
        seed=1,
        prompt_pool=prompt_pool,
    )
    report = engine.run(trace)
    assert report.trace_counts.get("decode") == 1, report.trace_counts
    pct = report.latency_percentiles()
    return engine, report, pct


def slo_pair(num_requests, max_new, slots, k, inter, trials=5):
    """DESIGN.md §9 acceptance measurement: frozen-ensemble baseline vs
    continuous overlapped refresh, both compile-warmed, same trace.  Runs
    in the CURRENT process — ``run()`` calls it through a forced-2-device
    child so ``RefreshScheduler`` parks the sampler on the spare device.

    The ratio is the MEDIAN over ``trials`` back-to-back (frozen, refresh)
    paired runs of the same trace on the same warmed engines: a p99 over
    ~10^2 requests is a near-max order statistic, and on a shared CPU box
    the frozen baseline alone varies ~40% trial to trial — a single-shot
    ratio would measure scheduler jitter, not refresh cost.  Pairing the
    runs in time and taking the median prices the refresh overhead while
    staying honest: every trial serves with continuous background refresh
    enabled, nothing is cherry-picked."""
    cfg = configs.get_config(ARCH, smoke=True)
    model = get_model(cfg)
    eng_f, rep_frozen, pct_frozen = _one_config(
        cfg, model, slots, k, inter, num_requests=num_requests, max_new=max_new,
        warm=True,
    )
    # refresh_chunk=2: on this CPU-quick config a smoke-model SGLD step is
    # ~30x a warmed decode tick, so a 16-step chunk would not reach a
    # single promotion inside the trace — the short chunk keeps the row
    # exercising real promotions while backpressure protects decode.
    # refresh_every=48: the forced-2-device child still shares ONE core, so
    # sampler micro-chunks contend with decode for cycles rather than truly
    # overlapping; the cadence sets the refresh duty cycle so the row prices
    # the scheduler's overlap machinery, not raw single-core contention —
    # the trace still lands several promotions end to end.
    eng_r, rep_slo, pct_slo = _one_config(
        cfg, model, slots, k, inter, num_requests=num_requests, max_new=max_new,
        warm=True, refresh=True, refresh_mode="overlapped", refresh_chunk=2,
        refresh_every=48,
    )
    trace = synthetic_trace(
        num_requests, vocab_size=cfg.vocab_size, prompt_lens=PROMPT_LENS,
        max_new=max_new, mean_interarrival=inter, seed=1,
    )
    pairs = [(rep_frozen, pct_frozen, rep_slo, pct_slo)]
    for _ in range(trials - 1):
        rep_f = eng_f.run(trace)
        rep_r = eng_r.run(trace)
        pairs.append((rep_f, rep_f.latency_percentiles(),
                      rep_r, rep_r.latency_percentiles()))
    ratios = sorted(
        pr[3]["latency_p99_s"] / max(pr[1]["latency_p99_s"], 1e-12) for pr in pairs
    )
    p99_ratio = float(np.median(ratios))
    # report the run whose ratio IS the median, so the row's p99/latency
    # fields are a real measured trace, not a synthetic mix of trials
    rep_frozen, pct_frozen, rep_slo, pct_slo = min(
        pairs,
        key=lambda pr: abs(
            pr[3]["latency_p99_s"] / max(pr[1]["latency_p99_s"], 1e-12) - p99_ratio
        ),
    )
    rf = rep_slo.refresher
    assert rf["device"], "scheduler found no spare device — overlap not measured"
    return {
        "slots": slots,
        "ensemble": k,
        "mean_interarrival": inter,
        "variant": "refresh_slo",
        # this row runs in a forced-CPU-device child: its own platform, not
        # the parent's, is what its times were taken on
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "refresh_every": 48,
        "sampler_chunk_steps": 2,
        "trials": trials,
        "requests": len(rep_slo.results),
        "step_us": round(1e6 * rep_slo.wall_s / max(rep_slo.decode_steps, 1), 1),
        "tokens_per_s": round(rep_slo.tokens_per_s, 2),
        "tokens_per_s_frozen": round(rep_frozen.tokens_per_s, 2),
        "p99_ratio": round(p99_ratio, 4),
        "p99_ratio_trials": [round(r, 4) for r in ratios],
        "latency_p99_frozen_s": round(pct_frozen["latency_p99_s"], 6),
        "snapshots_promoted": rep_slo.registry["promoted"],
        "snapshots_rejected": rep_slo.registry["rejected"],
        "sampler_device": rf["device"],
        "micro_chunks": rf["micro_chunks"],
        "micro_steps": rf["micro_steps"],
        "backpressure_ticks": rf["backpressure_ticks"],
        "flips_deferred": rf["flips_deferred"],
        "decode_steps_stalled": rf["decode_steps_stalled"],
        "per_refresh_wall_s": round(rf["per_refresh_wall_s"], 6),
        "pump_wall_s": round(rf["pump_wall_s"], 6),
        "wall_s": round(rep_slo.wall_s, 4),
        **{kk: round(v, 6) for kk, v in pct_slo.items()},
    }


def _kv_bytes(engine):
    """Dense: the static pool footprint (every slot pays max_seq up front).
    Paged: high-water page bytes actually touched over the run."""
    if engine.paged:
        return engine.pool.stats()["bytes_high_water"]
    return sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(engine.pool.caches)
    )


def run():
    cfg = configs.get_config(ARCH, smoke=True)
    model = get_model(cfg)
    grid = GRID_QUICK if QUICK else GRID_FULL
    num_requests = 8 if QUICK else 32
    max_new = 8 if QUICK else 24
    configs_out = []
    for slots, k, inter in grid:
        _, report, pct = _one_config(
            cfg, model, slots, k, inter, num_requests=num_requests, max_new=max_new
        )
        name = f"serve_s{slots}_k{k}_ia{inter:g}"
        step_us = 1e6 * report.wall_s / max(report.decode_steps, 1)
        emit(name, step_us, f"{report.tokens_per_s:.1f}tok/s")
        configs_out.append(
            {
                "slots": slots,
                "ensemble": k,
                "mean_interarrival": inter,
                "requests": len(report.results),
                "total_tokens": report.total_tokens,
                "decode_steps": report.decode_steps,
                "wall_s": round(report.wall_s, 4),
                "tokens_per_s": round(report.tokens_per_s, 2),
                "decode_traces": report.trace_counts.get("decode"),
                **{kk: round(v, 6) for kk, v in pct.items()},
            }
        )
    # dense vs paged (± prefix sharing) on the middle configuration, over a
    # prompt-pool trace so sharing has something to hit
    slots, k, inter = grid[1]
    pool_size = 3
    for variant, kw in (
        ("dense", {}),
        ("paged", {"paged": True, "block_size": 8}),
        ("paged_noshare", {"paged": True, "block_size": 8, "prefix_sharing": False}),
    ):
        engine, report, pct = _one_config(
            cfg, model, slots, k, inter, num_requests=num_requests,
            max_new=max_new, prompt_pool=pool_size, **kw,
        )
        kv = _kv_bytes(engine)
        per_req = kv / max(len(report.results), 1)
        st = engine.pool.stats()
        hit_rate = st.get("prefix_hit_rate", 0.0)
        emit(
            f"serve_s{slots}_k{k}_{variant}",
            1e6 * report.wall_s / max(report.decode_steps, 1),
            f"{per_req / 1024:.1f}KiB/req",
        )
        configs_out.append(
            {
                "slots": slots,
                "ensemble": k,
                "mean_interarrival": inter,
                "variant": variant,
                "prompt_pool": pool_size,
                "requests": len(report.results),
                "total_tokens": report.total_tokens,
                "tokens_per_s": round(report.tokens_per_s, 2),
                "wall_s": round(report.wall_s, 4),
                "kv_bytes": int(kv),
                "kv_bytes_per_request": round(per_req, 1),
                "prefix_hit_rate": round(float(hit_rate), 4),
                "prefix_hits": st.get("prefix_hits", 0),
                "blocks_high_water": st.get("blocks_high_water"),
                "decode_traces": report.trace_counts.get("decode"),
                **{kk: round(v, 6) for kk, v in pct.items()},
            }
        )
    # price the live-refresh path on the middle configuration
    _, report, pct = _one_config(
        cfg, model, slots, k, inter, num_requests=num_requests, max_new=max_new, refresh=True
    )
    emit(
        f"serve_s{slots}_k{k}_refresh",
        1e6 * report.wall_s / max(report.decode_steps, 1),
        f"{report.tokens_per_s:.1f}tok/s",
    )
    configs_out.append(
        {
            "slots": slots,
            "ensemble": k,
            "mean_interarrival": inter,
            "refresh_every": 8,
            "snapshots_promoted": report.registry["promoted"],
            "snapshots_rejected": report.registry["rejected"],
            "refresh_wall_s": report.refresher["refresh_wall_s"],
            "tokens_per_s": round(report.tokens_per_s, 2),
            "wall_s": round(report.wall_s, 4),
            **{kk: round(v, 6) for kk, v in pct.items()},
        }
    )
    # DESIGN.md §9 acceptance row: continuous *overlapped* refresh vs the
    # frozen baseline, in a forced-2-device child so the sampler has a
    # spare device (the parent backend is already locked to one)
    here = Path(__file__).resolve().parent
    # longer trace than the latency grid: enough decode ticks for several
    # promotions to land at the sampler's (backpressured) natural rate
    slo_requests = 64 if QUICK else 96
    child_src = textwrap.dedent(
        f"""
        import json, sys
        sys.path[:0] = [{str(here)!r}, {str(here.parent / "src")!r}]
        import serve_engine
        row = serve_engine.slo_pair({slo_requests}, {max_new}, {slots}, {k}, {inter})
        print("SLO=" + json.dumps(row), flush=True)
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", child_src],
        env=forced_device_env(2), capture_output=True, text=True, timeout=1200,
    )
    if out.returncode != 0:
        raise RuntimeError(f"refresh_slo child failed:\n{out.stderr[-3000:]}")
    row = json.loads(
        [ln for ln in out.stdout.splitlines() if ln.startswith("SLO=")][-1][4:]
    )
    emit(
        f"serve_s{slots}_k{k}_refresh_slo",
        row["step_us"],
        f"{row['tokens_per_s']:.1f}tok/s p99x{row['p99_ratio']:.2f}",
    )
    configs_out.append(row)
    record("serve", {"arch": ARCH, "configs": configs_out})
    return {"num_configs": len(configs_out)}
