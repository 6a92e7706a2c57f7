"""Roofline share of the paged decode attention kernel: the least time
its work could take on the chip over the device time of the ops named
``paged_attention``.  The work comes from the ``kv_pages`` of each
``serve.decode_tick`` span, the pages of the live slots up to each one's
position: each page's bfloat16 K and V rows for every kv-head, read once
per layer and member, plus each live slot's query and output rows; the
FLOPs are QK^T and PV over those pages' positions for every query head.
The kernel moves each of these bytes at least once, so the share cannot
pass 100%.  Bytes bind by about two orders of magnitude."""

BF16 = 2  # bytes of a K/V page row and of a query row (kv_dtype, compute_dtype)


def read(ctx):
    secs = sum(v for name, v in ctx["trace"]["ops"].items() if "paged_attention" in name)
    ticks = [a for ph, name, _c, _t, _d, a in ctx["events"]
             if ph == "X" and name == "serve.decode_tick" and "kv_pages" in a]
    if not secs or not ticks:
        return None
    cfg, pk = ctx["cfg"], ctx["peak"]
    dep = cfg["deployment"]
    per_layer = dep["members"] * cfg["num_hidden_layers"]
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    pages = sum(a["kv_pages"] for a in ticks)
    rows = sum(a["active"] for a in ticks)
    positions = pages * dep["block_size"]
    nbytes = per_layer * BF16 * (2 * positions * hkv * d + 2 * rows * hq * d)
    flops = per_layer * 4.0 * positions * hq * d
    least = max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / secs
