"""The tick-clock sweep that finds a serving cell's knee, at a small size."""
import tiny


def test_knee_sweep_reports_each_rate():
    import knee

    rows = list(knee.sweep(tiny.serve_cell(), [0.1, 0.4], ticks=40))
    assert [r["rate_per_tick"] for r in rows] == [0.1, 0.4]
    assert all(r["sent"] > 0 and 0 < r["mean_active"] <= r["slots"] for r in rows), rows
