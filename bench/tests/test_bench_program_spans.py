"""The per-layer metrics read from the program's own spans: each reader on a
hand-written ring, on an empty one, and on a ring from a program that does
not record its spans (they are left out of the result line); and, on a tiny
engine, the first token's wait as the engine records it against the wait
``serve.first_token_waits`` takes from the ring."""
import pytest

import harness
import serve

NEW = ("tick_host_ms", "first_token_wait_ms", "admit_queue_ms")


def _x(name, ts, dur, **args):
    return ("X", name, "serve", ts, dur, args)


def _i(name, ts, **args):
    return ("i", name, "serve", ts, 0, args)


# two ticks and two admits, times in ns: dispatch 1.0 and 3.0 ms, collect
# 0.5 and 1.5 ms; first-token waits 40 and 20 ms; queued 0.5 and 2.5 ms
RING = [
    _x("serve.admit", 0, 2_000_000, rid=0, slot=0, prompt_len=8, step=0,
       queued_ms=0.5, page_waits=0),
    _i("serve.first_token", 42_000_000, rid=0, slot=0, wait_ms=40.0),
    _x("serve.decode_tick", 50_000_000, 130_000_000, step=0, active=1),
    _x("serve.tick.dispatch", 50_000_010, 1_000_000),
    _x("serve.tick.fetch", 51_500_000, 128_000_000),
    _x("serve.tick.collect", 180_000_020, 500_000),
    _x("serve.admit", 181_000_000, 2_000_000, rid=1, slot=1, prompt_len=8, step=1,
       queued_ms=2.5, page_waits=1),
    _i("serve.first_token", 203_000_000, rid=1, slot=1, wait_ms=20.0),
    _x("serve.decode_tick", 204_000_000, 130_000_000, step=1, active=2),
    _x("serve.tick.dispatch", 204_000_010, 3_000_000),
    _x("serve.tick.fetch", 207_500_000, 126_000_000),
    _x("serve.tick.collect", 334_000_020, 1_500_000),
]

# the same run as a program without these spans records it
PARENT_RING = [
    _x("serve.admit", 0, 2_000_000, rid=0, slot=0, prompt_len=8, step=0),
    _x("serve.decode_tick", 50_000_000, 130_000_000, step=0, active=1),
    _i("serve.retire", 181_000_000, rid=0, slot=0, tokens=2, eos=False),
]


def _read(names, events):
    return harness.read_per_layer([{"name": n, "unit": "ms"} for n in names],
                                  {"events": events})


@pytest.mark.parametrize("name, value", [
    ("tick_host_ms", (1.0 + 0.5 + 3.0 + 1.5) / 2),
    ("first_token_wait_ms", 30.0),
    ("admit_queue_ms", 1.5),
])
def test_reader_on_a_hand_written_ring(name, value):
    assert _read([name], RING) == {name: {"value": pytest.approx(value), "unit": "ms"}}


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_an_empty_ring(name):
    assert _read([name], []) == {}


def test_readers_find_nothing_where_the_program_records_no_such_span():
    got = _read(NEW + ("decode_tick_ms",), PARENT_RING)
    assert got == {"decode_tick_ms": {"value": pytest.approx(130.0), "unit": "ms"}}


def test_the_serving_cell_reads_the_new_metrics():
    cell = harness.load_cell("serve.chat")
    layer = {m["name"]: m for m in cell["per_layer"]}
    for name in NEW:
        assert layer[name]["source"] == "program_span" and layer[name]["unit"] == "ms"
    assert harness.load_cell("sample.sghmc1")["per_layer"] and not (
        set(NEW) & {m["name"] for m in harness.load_cell("sample.sghmc1")["per_layer"]})


def test_engine_first_token_wait_is_the_rings_gap():
    from repro import configs
    from repro.models import get_model, init_params
    from repro.obs import trace as obs_trace
    from repro.serve.engine import ServeEngine, synthetic_trace

    import jax

    cfg = configs.get_config("qwen3-0.6b", smoke=True).replace(
        vocab_size=64, d_model=32, num_layers=2, num_heads=2, num_kv_heads=1,
        head_dim=16, d_ff=48)
    model = get_model(cfg)
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    members = jax.vmap(lambda k: init_params(model.param_specs(cfg), k))(keys)
    engine = ServeEngine(cfg, model, members, num_slots=2, max_seq=24, paged=True,
                         block_size=8)
    reqs = synthetic_trace(4, vocab_size=cfg.vocab_size, prompt_lens=(5,), max_new=4,
                           mean_interarrival=1.0, seed=1)
    tracer = obs_trace.enable(capacity=1 << 12)
    try:
        report = engine.run(reqs)
    finally:
        obs_trace.disable()
    events = tracer.events()
    gaps = serve.first_token_waits(events)
    waits = {a["rid"]: a["wait_ms"] for ph, n, _c, _t, _d, a in events
             if n == "serve.first_token"}
    assert sorted(gaps) == sorted(waits) == [r.rid for r in report.results]
    for rid, wait_ms in waits.items():
        # the ring's gap ends at the instant, a few clock reads after the
        # engine's own reading of the token held
        assert gaps[rid] * 1e3 == pytest.approx(wait_ms, abs=0.5)
    mean = _read(["first_token_wait_ms"], events)["first_token_wait_ms"]["value"]
    assert mean == pytest.approx(sum(waits.values()) / len(waits))
