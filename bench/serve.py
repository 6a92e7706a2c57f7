"""A serving cell: the K-member ensemble behind ``ServeEngine``, offered a
traffic mix open-loop on the engine's tick clock.

Set-up makes the members from the seed, builds the engine, and compiles
every program the window will run (an admit per prompt bucket, the decode
tick, the end-of-run truncation).  The window is one ``ServeEngine.run``
over the mix's requests, cut by ``max_steps`` so that it lasts about the
run's seconds.  Afterwards a sample of the finished requests is checked
against the float32 reference.
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


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file (its
    architecture module's)."""
    return harness.arch(cfg).model_config(cfg)


def pool_blocks(cfg: dict, dep: dict) -> int:
    """Pages of the paged KV pool: ``kv_pool_gib`` of pages for all
    members, plus the sink page."""
    per_block = dep["members"] * dep["block_size"] * harness.arch(cfg).kv_bytes_per_token(cfg)
    return int(dep["kv_pool_gib"] * 2**30 // per_block) + 1


def build(cfg: dict, mix: dict, seed: int, device):
    import jax

    from repro.models import get_model
    from repro.serve.engine import ServeEngine

    dep = cfg["deployment"]
    mcfg = model_config(cfg)
    model = get_model(mcfg)
    weights.check_layout(cfg, model.param_specs(mcfg))
    members = weights.make(cfg, seed, dep["members"],
                           out_sharding=jax.sharding.SingleDeviceSharding(device))
    engine = ServeEngine(
        mcfg, model, members, num_slots=mix["slots"], max_seq=mix["max_seq"],
        bma=dep["bma"], eos_id=dep["eos_id"], seed=seed % (2**31), paged=dep["paged"],
        block_size=dep["block_size"], num_blocks=pool_blocks(cfg, dep),
        fused_select=dep["fused_select"],
    )
    return engine


def _requests(rows):
    from repro.serve.engine import Request

    return [Request(rid=rid, prompt=p, max_new=m, arrival_step=a) for rid, p, m, a in rows]


# the window's ticks come in whole multiples of this, so that timing noise
# in the warm-up does not change the requests a run serves
TICK_QUANTUM = 16
WARM_REPEATS = 3


def warm_up(engine, mix: dict, cfg: dict, device) -> dict:
    """Compile every program the window runs, then time a tick and an
    admit per bucket on warm programs (medians over a few repeats).
    Returns the timings."""
    import jax
    import jax.numpy as jnp

    buckets = sorted(mix["prompt"]["buckets"])
    rng = np.random.default_rng(0)
    rows = [(i, rng.integers(0, cfg["vocab_size"], L).astype(np.int32), 9, 0)
            for i, L in enumerate(buckets)]
    engine.run(_requests(rows))  # compiles admit per bucket and the decode tick
    # run() marks the slots still in flight at max_steps done with one
    # scatter whose index length is their count: compile each count now
    S = engine.pool.num_slots
    done = jax.device_put(jnp.ones((S,), bool), device)
    for n in range(1, S + 1):
        done.at[jnp.asarray(list(range(n)), jnp.int32)].set(True).block_until_ready()
    admits, ticks = {}, []
    for _ in range(WARM_REPEATS):  # warm: time it
        res = sorted(engine.run(_requests(rows)).results, key=lambda r: r.first_token_s)
        prev = 0.0
        for r in res:  # admitted one after another at tick 0, each ends in a sync
            admits.setdefault(r.prompt_len, []).append(r.first_token_s - prev)
            prev = r.first_token_s
        ticks.append((res[-1].latency_s - res[-1].first_token_s) / (res[-1].num_tokens - 1))
    return {"admit_s": {L: float(np.median(v)) for L, v in admits.items()},
            "tick_s": float(np.median(ticks))}


def planned_ticks(mix: dict, timing: dict, seconds: float) -> int:
    """Ticks that fill ``seconds``: a tick plus the admits that arrive
    during it, at the mix's rate and prompt lengths."""
    prompts, _, _ = traffic.block_layout(mix)
    admit = float(np.mean([timing["admit_s"][int(L)] for L in prompts]))
    per_tick = timing["tick_s"] + mix["arrival"]["rate_per_tick"] * admit
    return max(TICK_QUANTUM * round(seconds / per_tick / TICK_QUANTUM), TICK_QUANTUM)


def visible_times(events, t0_ns: int) -> dict:
    """Tick -> host time (s after t0) at which the engine's loop reached it,
    from the program's ``serve.decode_tick`` spans."""
    out = {}
    for ph, name, _cat, ts, _dur, args in events:
        if ph == "X" and name == "serve.decode_tick":
            out.setdefault(int(args["step"]), (ts - t0_ns) / 1e9)
    return out


def first_token_waits(events) -> dict:
    """rid -> seconds from the end of its ``serve.admit`` span to the next
    thing the engine's host loop did.  The engine reads its clock for a
    request's first token before it fetches the token from the device
    (``int(tok)`` follows ``now = wall()`` in ``ServeEngine._do_admit``), so
    its ``first_token_s`` leaves out the prefill's device time; the host
    holds the token only once that fetch returns, before its next event."""
    starts = np.sort(np.asarray([ts for _ph, _n, _c, ts, _d, _a in events], np.int64))
    out = {}
    for ph, name, _c, ts, dur, args in events:
        if ph == "X" and name == "serve.admit":
            end = ts + dur
            i = np.searchsorted(starts, end, side="left")
            if i < len(starts):
                out[int(args["rid"])] = (starts[i] - end) / 1e9
    return out


def end_to_end(report, requests, events, t0_ns: int) -> tuple[dict, dict]:
    """The cell's end-to-end metrics and the request counts behind them.
    Every request arrived before the cut-off (``requests`` holds no other);
    one still queued there counts with the wait it had so far.  A
    request's first token counts from when it was due (the engine's loop
    reached its arrival tick) to when the host held it."""
    window = report.wall_s
    served = {r.rid for r in report.results}
    waits = first_token_waits(events)
    first = {r.rid: r.first_token_s + waits.get(r.rid, 0.0) for r in report.results}
    ttft = list(first.values())
    ticks = visible_times(events, t0_ns)
    steps = sorted(ticks)
    queued = 0
    for rid, _p, _m, arrival in requests:
        if rid in served:
            continue
        later = [s for s in steps if s >= arrival]
        ttft.append(window - (ticks[later[0]] if later else window))
        queued += 1
    tpot = [(r.latency_s - first[r.rid]) / (r.num_tokens - 1) * 1e3
            for r in report.results if r.num_tokens >= 2]
    metrics = {
        "serve_tokens_per_s": report.total_tokens / window,
        "ttft_p90_s": harness.percentile(ttft, 90),
        "tpot_p90_ms": harness.percentile(tpot, 90),
    }
    counts = {"sent": len(requests), "completed": sum(not r.truncated for r in report.results),
              "truncated": sum(r.truncated for r in report.results), "queued_at_cut": queued,
              "ttft_samples": len(ttft), "tpot_samples": len(tpot)}
    return metrics, counts


def check_sample(report, mix: dict, seed: int) -> list:
    """Finished requests to compare with the reference: the longest, then
    others drawn from the seed."""
    done = [r for r in report.results if not r.truncated]
    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt_len + r.num_tokens)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC4EC]))
    pick = rng.permutation(len(rest))[: max(mix["check"]["requests"] - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(cfg: dict, seed: int, sample, prompts: dict, max_seq: int, device,
                   precisions=("f32",)) -> dict:
    """For each precision, the widest gap over the sample: at each position
    where a token was served, the reference's best log-prob minus the
    reference's log-prob of the token chosen there.  "f32" judges the
    served tokens; a lower precision judges the tokens it would choose
    itself (the control)."""
    import jax
    import jax.numpy as jnp

    ref = harness.reference(cfg)
    K = cfg["deployment"]["members"]
    members = weights.make(cfg, seed, K, out_sharding=jax.sharding.SingleDeviceSharding(device))
    rows = 256

    @jax.jit
    def member_hidden(params, tokens):
        return jax.vmap(lambda p: ref.hidden(cfg, p, tokens))(params)

    def block_logp(precision):
        @jax.jit
        def fn(params, h, start):
            hb = jax.lax.dynamic_slice_in_dim(h, start, rows, axis=1)  # (K, rows, D)
            lg = jax.vmap(lambda p, x: ref.logits(cfg, p, x, precision))(params, hb)
            return ref.mixture_logprobs(lg)
        return fn

    f32_block = block_logp("f32")
    low_blocks = {p: block_logp(p) for p in precisions if p != "f32"}
    low_hidden = {p: jax.jit(jax.vmap(lambda pp, t, p=p: ref.hidden(cfg, pp, t, p),
                                      in_axes=(0, None))) for p in low_blocks}
    gaps = {p: 0.0 for p in precisions}
    h_low = {}
    for r in sample:
        prompt = prompts[r.rid]
        served = np.asarray(r.tokens, np.int64)
        seq = np.zeros(max_seq + rows, np.int32)
        n_in = len(prompt) + len(served) - 1
        seq[: len(prompt)] = prompt
        seq[len(prompt): n_in] = served[:-1]
        toks = jnp.asarray(seq[:max_seq])
        h = jnp.pad(member_hidden(members, toks), ((0, 0), (0, rows), (0, 0)))
        for p, fn in low_hidden.items():
            h_low[p] = jnp.pad(fn(members, toks), ((0, 0), (0, rows), (0, 0)))
        first = len(prompt) - 1  # position whose next token is the first served
        for start in range(first, first + len(served), rows):
            n = min(rows, first + len(served) - start)
            lp = np.asarray(f32_block(members, h, start))[:n]
            best = lp.max(axis=-1)
            chosen = served[start - first: start - first + n]
            if "f32" in gaps:
                gaps["f32"] = max(gaps["f32"], float(np.max(best - lp[np.arange(n), chosen])))
            for p, fn in low_blocks.items():
                low = np.asarray(fn(members, h_low[p], start))[:n]
                pick = low.argmax(axis=-1)
                gaps[p] = max(gaps[p], float(np.max(best - lp[np.arange(n), pick])))
    return gaps


def run(cell: dict, seed: int, seconds: float, trace: bool, devices, t_start: float,
        compiles) -> dict:
    import jax

    from repro.obs import trace as obs_trace

    cfg, mix = cell["cfg"], cell["mix"]
    device = devices[0]
    engine = build(cfg, mix, seed, device)
    timing = warm_up(engine, mix, cfg, device)
    # traced or not, the window is the run's: the requests it finishes are
    # what the check compares
    max_steps = planned_ticks(mix, timing, seconds)
    rows = traffic.serve_requests(mix, seed, cfg["vocab_size"], until_tick=max_steps)
    prompts = {rid: p for rid, p, _m, _a in rows}
    requests = _requests(rows)
    mem = device.memory_stats() or {}
    note(f"device memory after warm-up: in use {mem.get('bytes_in_use', 0) / 2**30:.2f} GiB, "
         f"peak {mem.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
         f"{mem.get('bytes_limit', 0) / 2**30:.2f} GiB")
    note(f"warm-up: tick {timing['tick_s'] * 1e3:.2f} ms, admits "
         f"{ {k: round(v * 1e3, 2) for k, v in sorted(timing['admit_s'].items())} } ms; "
         f"max_steps {max_steps}; {len(requests)} requests; {compiles}")
    tracer = obs_trace.enable(capacity=1 << 20)
    compiles.mark()
    setup_s = harness.elapsed(t_start)
    with harness.Profile(trace) as prof:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0_ns = time.perf_counter_ns()
            report = engine.run(requests, max_steps=max_steps)
            t1_ns = time.perf_counter_ns()
    in_window = compiles.since_mark
    events = tracer.events()
    obs_trace.disable()
    metrics, counts = end_to_end(report, rows, events, t0_ns)
    occupancy = [args["active"] for ph, name, _c, _t, _d, args in events
                 if ph == "X" and name == "serve.decode_tick"]
    note(f"window {report.wall_s:.3f} s, {report.decode_steps} ticks, requests {counts}, "
         f"mean active slots {np.mean(occupancy) if occupancy else 0:.2f}, "
         f"compiles in window {in_window}, decode traces {report.trace_counts.get('decode')}")
    device_line = harness.device_info(devices)
    if trace:
        reduced = prof.reduce(1)
        ctx = {"kind": "serve", "cfg": cfg, "mix": mix, "report": report, "events": events,
               "t0_ns": t0_ns, "t1_ns": t1_ns, "trace": reduced, "peak": harness.peak(device.device_kind),
               "chips": 1, "flops": flops}
        out_metrics = harness.read_per_layer(cell["per_layer"], ctx)
        device_line.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    else:
        out_metrics = {m: {"value": v, "unit": u["unit"]} for m, v in metrics.items()
                       for u in cell["end_to_end"] if u["name"] == m}
        out_metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    sample = check_sample(report, mix, seed)
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    gaps = reference_gaps(cfg, seed, sample, prompts, mix["max_seq"], device)
    served_tokens = sum(r.num_tokens for r in sample)
    note(f"reference: {len(sample)} requests, {served_tokens} served tokens, "
         f"{time.perf_counter() - t_ref:.1f} s")
    # the widest gap (nats) by which a served token's reference log-prob lies
    # below the reference's best token at that position, against the mix's
    # limit; a window that finished no request has nothing to show correct
    checks = [("widest_gap_nats", gaps["f32"] if sample else float("inf"),
               mix["check"]["limits"]["widest_gap_nats"])]
    correct = all(v <= lim for _n, v, lim in checks)
    result = {"correct": bool(correct), "attempted": counts["sent"], "failed": 0,
              "metrics": out_metrics, "device": device_line}
    if trace:
        result["breakdown"] = reduced["breakdown"]
    return harness.finish(result, checks)
