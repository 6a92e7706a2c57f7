"""The trace reduction: its interval arithmetic and a whole reduction
worked by hand on a small two-chip trace, and the reading of a trace file
the profiler wrote."""
import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr


def test_union_and_overlap_by_hand():
    merged = tr._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert tr._length(merged) == 7
    assert tr._overlap(merged, tr._union([(2, 6), (8, 20)])) == 1 + 1 + 1


def test_collective_names():
    assert tr._is_collective("all-reduce.3") and tr._is_collective("all-reduce-start")
    assert not tr._is_collective("fusion.12")


# two chips, a window of 100 ns from t=1000; times in ns
PLANES = {
    "/host:CPU": {"python": [("bench.window", 1000, 1100), ("bench.unit", 1000, 1050),
                             ("dispatch", 1060, 1080)]},
    "/device:TPU:0": {
        "XLA Modules": [("jit_step", 990, 1040), ("jit_step", 1050, 1090)],
        "XLA Ops": [("while.1", 990, 1040), ("fusion.1", 990, 1010),
                    ("all-reduce.1", 1010, 1040), ("fusion.2", 1050, 1070),
                    ("bma_select", 1070, 1090)],
    },
    "/device:TPU:1": {
        "XLA Modules": [("jit_step", 1000, 1080)],
        "XLA Ops": [("fusion.1", 1000, 1030), ("all-reduce.1", 1030, 1080)],
    },
    "/device:TPU:2": {"XLA Ops": [("unused", 1000, 1100)]},
}


def test_reduction_by_hand():
    r = tr.reduce_planes(PLANES, num_devices=2, top=3)
    assert r["window_s"] == pytest.approx(100e-9)
    # chip 0 busy [1000,1040) + [1050,1090) = 80; chip 1 [1000,1080) = 80
    assert r["busy_s"] == pytest.approx(80e-9)
    # all-reduce: chip 0 30 ns, chip 1 50 ns, no leaf op beside either
    assert r["collective_s"] == pytest.approx(40e-9)
    assert r["collective_exposed_s"] == pytest.approx(40e-9)
    assert r["ops"]["all-reduce.1"] == pytest.approx(40e-9)
    assert r["ops"]["fusion.1"] == pytest.approx((10e-9 + 30e-9) / 2)  # clipped at 1000
    # the loop holds fusion.1 and the all-reduce: no time of its own
    assert r["ops"]["while.1"] == pytest.approx(0.0)
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])
    assert r["modules"]["jit_step"]["count"] == pytest.approx(1.5)
    assert r["breakdown"]["device_ops"][0] == ["all-reduce.1", pytest.approx(40e-9)]
    assert len(r["breakdown"]["device_ops"]) == 3
    # idle: chip 0 [1040,1050) in bench.unit, [1090,1100) in no span;
    # chip 1 [1080,1100) in no span
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == {"bench.unit": pytest.approx(5e-9), "host: no span": pytest.approx(15e-9)}


def test_a_trace_without_the_window_or_chips_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_planes({"/host:CPU": {"python": []}})
    with pytest.raises(ValueError, match="device planes"):
        tr.reduce_planes({"/host:CPU": {"python": [("bench.window", 0, 1)]}})


def test_load_reads_the_profilers_file(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    planes = tr.load(str(path))
    spans = tr._host_spans(planes)
    assert [s for s in spans if s[0] == tr.WINDOW]
    assert all(e >= s for _n, s, e in spans)


def _recorded_planes():
    """Three decode ticks and an admit of serve.chat on a TPU v5e (32 slots,
    max_seq 2560), cut from a --trace 1 run into ``load``'s plain form;
    the window span is set to the cut."""
    import gzip
    import json
    from pathlib import Path

    raw = json.loads(gzip.decompress(
        (Path(__file__).resolve().parents[1] / "data" / "serve_ticks.json.gz").read_bytes()))
    return {p: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
            for p, lines in raw.items()}


def test_innermost_span_matches_a_scan_of_every_span():
    planes = _recorded_planes()
    host = tr._host_spans(planes)
    lo, hi = [(s, e) for n, s, e in host if n == tr.WINDOW][0]
    points = [lo + (hi - lo) * k / 500 for k in range(501)]
    for t, got in zip(points, tr._innermost(host, points)):
        around = [(e - s, n) for n, s, e in host if s <= t < e and n != tr.WINDOW]
        assert got == (min(around)[1] if around else None), t


def test_recorded_chip_trace():
    r = tr.reduce_planes(_recorded_planes(), num_devices=1)
    assert r["window_s"] == pytest.approx(0.541858791)
    assert 0.9 < r["busy_s"] / r["window_s"] < 1.0
    decode = [m for n, m in r["modules"].items() if "_decode" in n]
    admit = [m for n, m in r["modules"].items() if "_admit" in n]
    assert [m["count"] for m in decode] == [3.0] and [m["count"] for m in admit] == [1.0]
    assert decode[0]["seconds"] == pytest.approx(0.5015, abs=1e-3)  # 167 ms a tick
    assert any("bma_select" in n for n in r["ops"])
    assert sum(r["ops"].values()) <= r["busy_s"] * 1.001
