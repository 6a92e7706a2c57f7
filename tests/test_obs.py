"""Observability battery (DESIGN.md §11): metrics registry, tracer ring
buffer, Perfetto export + schema validation, structured logging — and the
two load-bearing pins from the issue:

* **zero-cost when off** — a full engine run with the tracer disabled
  makes ZERO tracer clock reads (``trace._now`` is monkeypatched to
  count), produces bit-identical tokens to an instrumented run, and the
  one-compiled-decode-program pin survives instrumentation;
* **valid timeline when on** — a traced serve run with live refresh
  exports Chrome/Perfetto JSON containing the decode-tick, micro-chunk,
  flip/defer and (EC cadence) sync-collective spans, checked by the same
  validator ``scripts/ci.sh`` runs.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.log import get_logger
from repro.obs.sinks import MANIFEST_KEYS, run_manifest
from repro.obs.validate import REQUIRED, validate_manifest, validate_trace
from repro.run import ChainExecutor
from repro.serve.engine import (
    RefreshScheduler,
    ServeEngine,
    SnapshotRegistry,
    synthetic_trace,
)

from test_serve_engine import member_stack, tiny_cfg
from util import import_hypothesis

given, settings, st = import_hypothesis()


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Every test starts from the disabled NULL tracer and a fresh default
    registry, and cannot leak REPRO_LOG* into its neighbours."""
    monkeypatch.delenv("REPRO_LOG", raising=False)
    monkeypatch.delenv("REPRO_LOG_FORMAT", raising=False)
    obs_trace.disable()
    obs_metrics.reset_default()
    yield
    obs_trace.disable()
    obs_metrics.reset_default()


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_monotone(self):
        c = obs_metrics.Counter("x_total")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_last_write_wins(self):
        g = obs_metrics.Gauge("x")
        g.set(3)
        g.set(jnp.asarray(2.5))  # jnp scalars coerce
        assert g.value == 2.5

    def test_histogram_summary_and_quantiles(self):
        h = obs_metrics.Histogram("lat_s", lo=1e-3, hi=1e2, n=50)
        vals = [0.01 * (i + 1) for i in range(100)]  # 0.01 .. 1.0
        for v in vals:
            h.observe(v)
        s = h.summary()
        assert s["count"] == 100
        assert s["min"] == pytest.approx(0.01) and s["max"] == pytest.approx(1.0)
        assert s["mean"] == pytest.approx(float(np.mean(vals)))
        # log-spaced buckets: interpolated quantiles land within a bucket
        # width of the exact order statistic
        assert s["p50"] == pytest.approx(0.5, rel=0.3)
        assert s["p99"] == pytest.approx(1.0, rel=0.3)

    def test_histogram_edge_clamping(self):
        h = obs_metrics.Histogram("x_s", lo=1e-3, hi=1.0, n=8)
        h.observe(1e-9)  # underflow -> first bucket
        h.observe(1e9)  # overflow -> last bucket
        assert h.counts[0] == 1 and h.counts[-1] == 1
        assert math.isnan(obs_metrics.Histogram("y_s").quantile(0.5))

    def test_registry_type_mismatch_raises(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("a_total")
        with pytest.raises(TypeError):
            reg.gauge("a_total")

    def test_absorb_renames_and_is_idempotent(self):
        reg = obs_metrics.MetricsRegistry()
        legacy = {"num_slots": 4, "active": 2, "acquired": 17, "device": "cpu:0"}
        reg.absorb("serve.pool", legacy)
        reg.absorb("serve.pool", legacy)  # cumulative source: no double count
        snap = reg.snapshot()
        assert snap["serve.pool.slots"] == 4
        assert snap["serve.pool.slots_active"] == 2
        assert snap["serve.pool.slots_acquired_total"] == 17
        assert not any("device" in k for k in snap)  # non-numeric skipped
        assert reg._metrics["serve.pool.slots_acquired_total"].kind == "counter"
        assert reg._metrics["serve.pool.slots"].kind == "gauge"

    def test_absorb_passthrough_for_canonical_keys(self):
        reg = obs_metrics.MetricsRegistry()
        reg.absorb("serve.alloc", {"blocks_high_water": 7, "prefix_hits": 3})
        snap = reg.snapshot()
        assert snap["serve.alloc.blocks_high_water"] == 7
        assert snap["serve.alloc.prefix_hits_total"] == 3


# ---------------------------------------------------------------------------
# tracer ring buffer
# ---------------------------------------------------------------------------


def _fill(tr, n):
    for i in range(n):
        tr.instant(f"e{i}", cat="serve", i=i)


class TestTracerRing:
    def test_wraparound_keeps_newest_in_order(self):
        tr = obs_trace.Tracer(capacity=8)
        _fill(tr, 20)
        assert len(tr) == 8
        assert tr.dropped == 12
        assert [e[1] for e in tr.events()] == [f"e{i}" for i in range(12, 20)]
        ts = [e[3] for e in tr.events()]
        assert ts == sorted(ts)  # chronological after rotation

    def test_no_wrap_is_plain_prefix(self):
        tr = obs_trace.Tracer(capacity=8)
        _fill(tr, 3)
        assert len(tr) == 3 and tr.dropped == 0
        assert [e[1] for e in tr.events()] == ["e0", "e1", "e2"]

    @given(cap=st.integers(min_value=1, max_value=16),
           n=st.integers(min_value=0, max_value=50))
    @settings(max_examples=50, deadline=None)
    def test_wraparound_property(self, cap, n):
        tr = obs_trace.Tracer(capacity=cap)
        _fill(tr, n)
        assert len(tr) == min(n, cap)
        assert tr.dropped == max(0, n - cap)
        assert [e[1] for e in tr.events()] == [f"e{i}" for i in range(max(0, n - cap), n)]

    def test_wraparound_fallback_grid(self):
        # deterministic stand-in for the property test in bare envs
        for cap in (1, 2, 3, 7, 8):
            for n in (0, 1, cap - 1, cap, cap + 1, 3 * cap + 2):
                if n < 0:
                    continue
                tr = obs_trace.Tracer(capacity=cap)
                _fill(tr, n)
                assert len(tr) == min(n, cap)
                assert tr.dropped == max(0, n - cap)
                assert [e[1] for e in tr.events()] == [
                    f"e{i}" for i in range(max(0, n - cap), n)
                ]

    def test_span_records_duration(self):
        tr = obs_trace.Tracer(capacity=4)
        with tr.span("work", cat="executor", step=3):
            pass
        (ph, name, cat, ts, dur, args) = tr.events()[0]
        assert (ph, name, cat) == ("X", "work", "executor")
        assert dur >= 0 and args == {"step": 3}

    def test_install_restores_a_saved_tracer(self):
        # scoped measurements (the obs-overhead bench) must be able to hand
        # back whatever tracer --trace installed
        outer = obs_trace.enable(capacity=4)
        obs_trace.enable(capacity=4)  # stomps the module tracer
        assert obs_trace.get() is not outer
        assert obs_trace.install(outer) is outer
        assert obs_trace.get() is outer

    def test_disabled_tracer_hands_out_shared_noop(self):
        tr = obs_trace.Tracer(capacity=4, enabled=False)
        s1 = tr.span("a")
        s2 = tr.span("b")
        assert s1 is s2  # one shared object, no allocation per call
        with s1:
            pass
        tr.instant("c")
        assert len(tr) == 0


# ---------------------------------------------------------------------------
# chrome export + validator
# ---------------------------------------------------------------------------


MANIFEST_STUB = {k: (1 if k == "device_count" else "x") for k in MANIFEST_KEYS}


class TestExportAndValidate:
    def test_to_chrome_structure(self):
        tr = obs_trace.Tracer(capacity=16)
        with tr.span("serve.decode_tick", cat="serve", step=0):
            tr.instant("alloc.reserve", cat="alloc", slot=1)
        obj = tr.to_chrome(manifest=MANIFEST_STUB)
        assert obj["displayTimeUnit"] == "ms"
        assert obj["otherData"]["dropped_events"] == 0
        evs = obj["traceEvents"]
        assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
        tracks = {e["args"]["name"]: e["tid"] for e in evs if e.get("name") == "thread_name"}
        assert tracks == {"serve": 0, "alloc": 3}  # one track per category
        assert validate_trace(obj) == []

    def test_export_roundtrip(self, tmp_path):
        tr = obs_trace.Tracer(capacity=4)
        tr.instant("serve.admit", cat="serve")
        path = tmp_path / "trace.json"
        tr.export(path, manifest=MANIFEST_STUB)
        assert validate_trace(str(path)) == []

    def test_validator_catches_malformed_events(self):
        bad = {
            "traceEvents": [
                {"ph": "Z", "name": "a", "pid": 0, "tid": 0},  # bad phase
                {"ph": "X", "name": "b", "pid": 0, "tid": 0, "ts": 0.0},  # no dur
                {"ph": "i", "pid": 0, "tid": 0, "ts": 1.0, "s": "t"},  # no name
            ],
        }
        errs = validate_trace(bad)
        assert any("bad ph" in e for e in errs)
        assert any("non-negative dur" in e for e in errs)
        assert any("missing name" in e for e in errs)
        assert any("manifest" in e for e in errs)

    def test_validator_required_profiles(self):
        tr = obs_trace.Tracer(capacity=8)
        tr.instant("executor.chunk", cat="executor")
        obj = tr.to_chrome(manifest=MANIFEST_STUB)
        assert validate_trace(obj, REQUIRED["executor"]) == []
        errs = validate_trace(obj, REQUIRED["serve"])
        assert any("serve.decode_tick" in e for e in errs)

    def test_run_manifest_complete(self):
        m = run_manifest()
        assert validate_manifest(m) == []
        assert m["device_count"] >= 1
        assert m["backend"] in ("cpu", "gpu", "tpu")


# ---------------------------------------------------------------------------
# structured logging
# ---------------------------------------------------------------------------


class TestLogging:
    def test_human_format_and_levels(self, capsys, monkeypatch):
        log = get_logger("loop")
        log.info("step 3: nll=1.25", chains=4)
        monkeypatch.setenv("REPRO_LOG", "off")
        log.info("suppressed")
        out = capsys.readouterr().out
        assert out == "[loop] step 3: nll=1.25 chains=4\n"

    def test_warning_goes_to_stderr(self, capsys):
        get_logger("ckpt").warning("skipping bad.ckpt")
        cap = capsys.readouterr()
        assert cap.out == "" and "[ckpt] skipping bad.ckpt" in cap.err

    def test_debug_below_default_threshold(self, capsys, monkeypatch):
        log = get_logger("x")
        log.debug("hidden")
        monkeypatch.setenv("REPRO_LOG", "debug")
        log.debug("shown")
        assert capsys.readouterr().out == "[x] shown\n"

    def test_json_format(self, capsys, monkeypatch):
        monkeypatch.setenv("REPRO_LOG_FORMAT", "json")
        get_logger("serve").info("done", requests=6)
        rec = json.loads(capsys.readouterr().out)
        assert rec == {"level": "info", "logger": "serve", "msg": "done", "requests": 6}


# ---------------------------------------------------------------------------
# the zero-cost-when-off pins (executor + engine)
# ---------------------------------------------------------------------------


def _count_clock(monkeypatch):
    calls = {"n": 0}
    real = obs_trace._now

    def counting():
        calls["n"] += 1
        return real()

    monkeypatch.setattr(obs_trace, "_now", counting)
    return calls


def _executor_samples(steps=64):
    sampler = core.ec_sghmc(step_size=1e-2, alpha=1.0, sync_every=4)
    mu = jnp.array([1.0, -2.0])
    ex = ChainExecutor(sampler=sampler, grad_fn=lambda p, _b: p - mu,
                       trace_fn=lambda p: p, chunk_steps=16, key_mode="keys")
    keys = jax.random.split(jax.random.PRNGKey(0), steps)
    p0 = jnp.zeros((4, 2))
    res = ex.run(p0, sampler.init(p0), num_steps=steps, keys=keys)
    return np.asarray(res.trace)


class TestZeroCostOff:
    def test_executor_off_makes_no_clock_reads_and_is_bit_identical(self, monkeypatch):
        ref = _executor_samples()
        calls = _count_clock(monkeypatch)
        off = _executor_samples()
        assert calls["n"] == 0  # disabled tracer never touched the clock
        np.testing.assert_array_equal(ref, off)
        tr = obs_trace.enable(capacity=1 << 10)
        on = _executor_samples()
        assert calls["n"] > 0
        np.testing.assert_array_equal(ref, on)  # samples don't see the tracer
        assert "executor.chunk" in tr.names() and "executor.settle" in tr.names()

    def test_engine_off_vs_on_bit_identical_and_pin_holds(self, monkeypatch):
        cfg = tiny_cfg()
        from repro.models import get_model

        model = get_model(cfg)
        stack = member_stack(cfg, model, 2)

        def serve():
            engine = ServeEngine(cfg, model, stack, num_slots=2, max_seq=16)
            reqs = synthetic_trace(4, vocab_size=cfg.vocab_size, prompt_lens=(5,),
                                   max_new=6, mean_interarrival=2.0, seed=9)
            report = engine.run(reqs)
            assert report.trace_counts["decode"] == 1, report.trace_counts
            return [np.asarray(r.tokens) for r in sorted(report.results, key=lambda r: r.rid)]

        calls = _count_clock(monkeypatch)
        toks_off = serve()
        assert calls["n"] == 0  # full engine run, zero tracer clock reads
        tr = obs_trace.enable(capacity=1 << 12)
        toks_on = serve()
        for a, b in zip(toks_off, toks_on):
            np.testing.assert_array_equal(a, b)
        assert {"serve.decode_tick", "serve.admit", "serve.retire"} <= tr.names()

    def test_enabled_tracer_records_host_scalars_only(self):
        # recording must never capture device arrays (that would add host
        # syncs at export time); every span/instant arg is a host scalar
        tr = obs_trace.enable(capacity=1 << 12)
        _executor_samples()
        for ev in tr.events():
            for v in ev[5].values():
                assert not isinstance(v, jnp.ndarray), ev


# ---------------------------------------------------------------------------
# traced serve run with live refresh (the enabled-path acceptance)
# ---------------------------------------------------------------------------


def _refresh_engine(sampler, sync_every=None, k=2):
    cfg = tiny_cfg()
    from repro.models import get_model

    model = get_model(cfg)
    stack = member_stack(cfg, model, k)
    center = jax.tree.map(lambda x: x[0], stack)
    grad_fn = lambda p: jax.tree.map(lambda x, c: 2500.0 * (x - c), p, center)
    start = jax.tree.map(lambda x: jnp.broadcast_to(x[0][None], x.shape) + 0.0, stack)
    reg = SnapshotRegistry(stack)
    sched = RefreshScheduler(
        reg, sampler, grad_fn, start, key=jax.random.PRNGKey(8), chunk_steps=4,
        sync_every=sync_every,
    )
    engine = ServeEngine(cfg, model, reg, num_slots=2, max_seq=24,
                         refresher=sched, refresh_every=2)
    reqs = synthetic_trace(6, vocab_size=cfg.vocab_size, prompt_lens=(5,),
                           max_new=8, mean_interarrival=1.5, seed=4)
    return engine, reqs


class TestTracedServe:
    def test_traced_serve_with_live_refresh_exports_valid_profile(self, tmp_path):
        tr = obs_trace.enable(capacity=1 << 14)
        engine, reqs = _refresh_engine(core.sgld(step_size=8e-5))
        report = engine.run(reqs)
        assert report.trace_counts["decode"] == 1
        path = tmp_path / "trace.json"
        tr.export(path)
        assert validate_trace(str(path), REQUIRED["serve"]) == []

    def test_traced_ec_serve_reconstructs_sync_collectives(self, tmp_path):
        tr = obs_trace.enable(capacity=1 << 14)
        engine, reqs = _refresh_engine(
            core.ec_sghmc(step_size=8e-5, alpha=1.0, sync_every=4), sync_every=4
        )
        engine.run(reqs)
        obj = tr.export(tmp_path / "trace.json")
        assert validate_trace(obj, REQUIRED["serve_ec"]) == []
        syncs = [e for e in obj["traceEvents"]
                 if e.get("name") == "sampler.sync_collective"]
        # host-reconstructed at the static cadence: strictly increasing
        # multiples of sync_every
        steps = [e["args"]["step"] for e in syncs]
        assert steps and steps == sorted(steps)
        assert all(s % 4 == 0 for s in steps)

    def test_engine_run_absorbs_canonical_metrics(self):
        engine, reqs = _refresh_engine(core.sgld(step_size=8e-5))
        report = engine.run(reqs)
        snap = obs_metrics.default_registry().snapshot()
        assert snap["serve.engine.decode_steps_total"] == report.decode_steps
        assert snap["serve.engine.tokens_total"] == report.total_tokens
        assert snap["serve.pool.slots"] == 2
        assert snap["serve.refresh.micro_chunks_total"] >= 1
        assert snap["serve.request.latency_s"]["count"] == len(report.results)


# ---------------------------------------------------------------------------
# program spans: profiler annotations, the split tick, the first token and
# the admit queue
# ---------------------------------------------------------------------------


class _SpyAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` and logs its use."""

    log: list = []

    def __init__(self, name, **kwargs):
        assert not kwargs  # name only
        self.name = name
        self.log.append(("new", name))

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.fixture
def spy_annotation(monkeypatch):
    monkeypatch.setattr(_SpyAnnotation, "log", [])
    monkeypatch.setattr(obs_trace, "_annotation", _SpyAnnotation)
    return _SpyAnnotation.log


def _tiny_serve(num_requests=4, *, paged=False, num_blocks=None, max_new=6):
    cfg = tiny_cfg()
    from repro.models import get_model

    model = get_model(cfg)
    engine = ServeEngine(cfg, model, member_stack(cfg, model, 2), num_slots=2, max_seq=24,
                         paged=paged, block_size=8, num_blocks=num_blocks)
    reqs = synthetic_trace(num_requests, vocab_size=cfg.vocab_size, prompt_lens=(5,),
                           max_new=max_new, mean_interarrival=1.0, seed=3)
    return engine.run(reqs)


def _spans(events, name):
    return [e for e in events if e[0] == "X" and e[1] == name]


class TestProgramSpans:
    def test_enabled_span_enters_and_exits_a_profiler_annotation(self, spy_annotation):
        tr = obs_trace.Tracer(capacity=8)
        with tr.span("serve.decode_tick", cat="serve", step=1):
            assert spy_annotation == [("new", "serve.decode_tick"), ("enter", "serve.decode_tick")]
        assert spy_annotation[-1] == ("exit", "serve.decode_tick")
        assert tr.names() == {"serve.decode_tick"}
        # annotate(): the profiler sees the region, the ring does not
        with tr.annotate("serve.first_token.fetch"):
            pass
        assert spy_annotation[-2:] == [("enter", "serve.first_token.fetch"),
                                       ("exit", "serve.first_token.fetch")]
        assert len(tr) == 1

    def test_disabled_tracer_makes_no_annotation_and_reads_no_clock(
            self, spy_annotation, monkeypatch):
        calls = _count_clock(monkeypatch)
        tr = obs_trace.Tracer(capacity=4, enabled=False)
        with tr.span("a"), tr.annotate("b"):
            tr.instant("c")
        _tiny_serve(paged=True)  # the module tracer is the disabled NULL
        assert spy_annotation == [] and calls["n"] == 0

    def test_each_decode_tick_holds_one_dispatch_and_one_fetch(self):
        tr = obs_trace.enable(capacity=1 << 14)
        report = _tiny_serve(paged=True)
        events = tr.events()
        ticks = _spans(events, "serve.decode_tick")
        assert len(ticks) == report.decode_steps > 0
        inside = lambda e, t: t[3] <= e[3] and e[3] + e[4] <= t[3] + t[4]
        for name in ("serve.tick.dispatch", "serve.tick.fetch"):
            spans = _spans(events, name)
            assert len(spans) == len(ticks)
            for t in ticks:
                assert sum(inside(e, t) for e in spans) == 1, (name, t)
        # collect follows its tick, outside it
        collects = _spans(events, "serve.tick.collect")
        assert len(collects) == len(ticks)
        for t, c in zip(ticks, collects):
            assert c[3] >= t[3] + t[4]

    def test_decode_tick_counts_the_live_pages(self):
        """``kv_pages`` of each tick's span is the pages the tick attends:
        ceil((ctx + 1) / bs) summed over the slots that are not done, from
        the tables, positions and done mask the tick hands the device."""
        cfg = tiny_cfg()
        from repro.models import get_model

        model = get_model(cfg)
        engine = ServeEngine(cfg, model, member_stack(cfg, model, 2), num_slots=3,
                             max_seq=24, paged=True, block_size=4)
        sent, decode = [], engine._decode

        def spy(members, pools, tokens, done, budget, tables, ctx, key):
            sent.append((np.asarray(done), np.asarray(ctx)))
            return decode(members, pools, tokens, done, budget, tables, ctx, key)

        engine._decode = spy
        reqs = synthetic_trace(5, vocab_size=cfg.vocab_size, prompt_lens=(3, 6, 9),
                               max_new=7, mean_interarrival=1.0, seed=4)
        tr = obs_trace.enable(capacity=1 << 14)
        engine.run(reqs)
        got = [e[5]["kv_pages"] for e in _spans(tr.events(), "serve.decode_tick")]
        want = [int(sum(c // 4 + 1 for c in ctx[~done])) for done, ctx in sent]
        assert got == want and len(set(got)) > 2

    def test_first_token_is_the_first_ring_event_after_its_admit(self):
        tr = obs_trace.enable(capacity=1 << 14)
        report = _tiny_serve(paged=True)
        events = tr.events()
        admits = {e[5]["rid"]: i for i, e in enumerate(events)
                  if e[0] == "X" and e[1] == "serve.admit"}
        firsts = [e[5]["rid"] for e in events if e[1] == "serve.first_token"]
        assert sorted(firsts) == sorted(admits) == [r.rid for r in report.results]
        starts = sorted(e[3] for e in events)
        for rid, i in admits.items():
            admit = events[i]
            end = admit[3] + admit[4]
            # the first event to START after the admit ends, in time and in
            # the ring, is this request's first token
            nxt = min((e for e in events if e[3] >= end), key=lambda e: e[3])
            assert nxt[1] == "serve.first_token" and nxt[5]["rid"] == rid
            assert nxt[3] == starts[np.searchsorted(starts, end)]
            assert nxt[5]["wait_ms"] >= 0.0 and nxt[5]["slot"] == admit[5]["slot"]

    def test_first_token_ready_counts_the_fetch(self):
        report = _tiny_serve()
        for r in report.results:
            assert r.first_token_ready_s >= r.first_token_s >= 0.0
        ready = np.asarray([r.first_token_ready_s for r in report.results])
        pct = report.latency_percentiles()
        assert pct["first_token_p50_s"] == pytest.approx(float(np.percentile(ready, 50)))
        hist = obs_metrics.default_registry().snapshot()["serve.request.first_token_s"]
        assert hist["sum"] == pytest.approx(float(ready.sum()))

    def test_page_waits_and_queued_ms(self):
        # pages for one request at a time (2 of 2 usable pages each) beside
        # two free slots: the second of two requests waits for pages.  The
        # first run warms the host's one-off work out of queued_ms
        _tiny_serve(4, paged=True, num_blocks=3, max_new=8)
        obs_metrics.reset_default()
        tr = obs_trace.enable(capacity=1 << 14)
        report = _tiny_serve(4, paged=True, num_blocks=3, max_new=8)
        admits = sorted((e[5] for e in _spans(tr.events(), "serve.admit")),
                        key=lambda a: a["page_waits"])
        assert len(admits) == len(report.results) == 4
        waits = [a["page_waits"] for a in admits]
        assert waits[-1] > 0
        queued = [a["queued_ms"] for a in admits]
        assert all(q >= 0.0 for q in queued)
        # queued_ms grows with the ticks waited for pages
        for a, b in zip(admits, admits[1:]):
            if b["page_waits"] > a["page_waits"]:
                assert b["queued_ms"] > a["queued_ms"]
        snap = obs_metrics.default_registry().snapshot()
        assert snap["serve.admit.page_waits_total"] == sum(waits)

