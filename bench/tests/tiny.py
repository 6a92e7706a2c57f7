"""Cells of the benchmark cut to a size the CPU runs in seconds: the same
files, the same harness, a small model and a small mix."""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
# limits at this size: the cells' limits were set from readings at their own
# size on the chip; at this size the sound program's change over three steps
# is more round-off than step (reads up to ~0.03), so the checks here hold
# it to limits a tenth of what each planted fault reads here
SAMPLE_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.1, "change_gap": 0.1}
# at this size the logits are nearly flat: sound runs read a widest gap of
# 0-0.003 nats and an altered token 0.14-0.63, so the serving check here
# holds the gap to its own limit, not the cell's
SERVE_LIMITS = {"widest_gap_nats": 0.05}
SMALL = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             intermediate_size=256, num_hidden_layers=2, vocab_size=512)


def _load(path):
    return json.loads((BENCH / path).read_text())


def serve_cell():
    cfg = _load("configs/qwen3-0.6b.ensemble-k2.json")
    cfg.update(SMALL)
    cfg["deployment"] = dict(cfg["deployment"], kv_pool_gib=0.002, fused_select=False)
    mix = _load("traffic/chat.json")
    mix.update(slots=4, max_seq=160, block_requests=8, check=dict(requests=3, limits=SERVE_LIMITS))
    mix["prompt"] = dict(median=32, sigma=0.8, buckets=[16, 32, 64])
    mix["output"] = dict(median=8, sigma=0.8, min=2, max=32)
    mix["arrival"] = dict(mix["arrival"], rate_per_tick=0.3)
    return {"name": "serve.tiny", "chips": 1, "cfg": cfg, "mix": mix, "per_layer": [],
            "end_to_end": [{"name": n, "unit": u} for n, u in (
                ("serve_tokens_per_s", "tokens/s"), ("ttft_p90_s", "s"), ("tpot_p90_ms", "ms"))]}


def sample_cell(traffic):
    cfg = _load("configs/qwen3-0.6b.posterior.json")
    cfg.update(SMALL)
    mix = _load(f"traffic/{traffic}.json")
    mix.update(batch=4, seq=32, check={"limits": SAMPLE_LIMITS})
    return {"name": "sample.tiny", "chips": mix["chains"], "cfg": cfg, "mix": mix,
            "per_layer": [], "end_to_end": []}
