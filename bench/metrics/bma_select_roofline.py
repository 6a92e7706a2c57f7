"""Roofline share of the fused BMA mixture-and-select kernel: the least
time its work could take on the chip (the larger of FLOPs over peak and
bytes over bandwidth, the work from the logits' shapes in ``flops.py``)
over the device time of its executions.  One execution per decode tick."""


def read(ctx):
    t = ctx["trace"]
    secs = sum(v for name, v in t["ops"].items() if "bma_select" in name)
    calls = sum(m["count"] for name, m in t["modules"].items() if "_decode" in name)
    if not secs or not calls:
        return None
    cfg, mix, pk = ctx["cfg"], ctx["mix"], ctx["peak"]
    fl, nbytes = ctx["flops"].bma_select_work(cfg["deployment"]["members"], mix["slots"],
                                              cfg["vocab_size"])
    least = max(fl / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least * calls / secs
