"""The paged attention kernel's roofline share, read from the ticks'
``kv_pages`` and the device time of the ops named ``paged_attention``: on a
hand-built context against the share worked out by hand, and nothing where
the trace holds no such op or the ticks carry no page count (a program
without the kernel)."""
import pytest

import harness

NAME = "paged_attention_roofline"
CFG = {"num_hidden_layers": 28, "num_attention_heads": 16, "num_key_value_heads": 8,
       "head_dim": 128, "deployment": {"members": 2, "block_size": 16}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _tick(ts, **args):
    return ("X", "serve.decode_tick", "serve", ts, 60_000_000, args)


def _ctx(events, ops):
    return {"events": events, "trace": {"ops": ops}, "cfg": CFG, "peak": PEAK}


def _read(ctx):
    return harness.read_per_layer([{"name": NAME, "unit": "%"}], ctx)


# two ticks: 800 and 900 live pages over 10 and 11 live slots
TICKS = [_tick(0, step=0, active=10, kv_pages=800),
         _tick(70_000_000, step=1, active=11, kv_pages=900)]
# the kernel's device time in the window, over two op names
OPS = {"%paged_attention.7 = bf16[2,20,16,128] custom-call(...)": 0.004,
       "%paged_attention.8 = bf16[2,20,16,128] custom-call(...)": 0.002,
       "%fusion.12 = f32[2,20,151936] fusion(...)": 9.0}


def test_share_worked_out_by_hand():
    # K and V of 1,700 pages x 16 positions x 8 kv-heads x 128 x 2 B, plus
    # query and output rows of 21 slots x 16 heads x 128 x 2 B, for each of
    # 28 layers x 2 members; bytes bind (FLOPs: 4 x 27,200 x 16 x 128 x 56)
    kv = 2 * 1700 * 16 * 8 * 128 * 2
    qo = 2 * 21 * 16 * 128 * 2
    least = 56 * (kv + qo) / 819e9
    assert 56 * 4.0 * 1700 * 16 * 16 * 128 / 197e12 < least / 50
    got = _read(_ctx(TICKS, OPS))
    assert got == {NAME: {"value": pytest.approx(100.0 * least / 0.006), "unit": "%"}}


@pytest.mark.parametrize("events, ops", [
    (TICKS, {"%fusion.12 = f32[2,20,151936] fusion(...)": 9.0}),
    ([_tick(0, step=0, active=10)], OPS),
    ([], OPS),
], ids=["no-kernel-op", "ticks-without-pages", "no-ticks"])
def test_nothing_to_read(events, ops):
    assert _read(_ctx(events, ops)) == {}


def test_the_serving_cell_reads_it():
    layer = {m["name"]: m for m in harness.load_cell("serve.chat")["per_layer"]}
    assert layer[NAME]["source"] == "device_trace" and layer[NAME]["moves"] == "tpot_p90_ms"
    assert NAME not in {m["name"] for m in harness.load_cell("sample.sghmc1")["per_layer"]}
