"""Model FLOP utilisation of serving: the FLOPs the served requests needed
(every member's prefill of each admitted prompt and its decode of each
generated token, from ``flops.py``) over the window times the chip's peak.
Slots that decode nothing add no useful FLOPs."""


def read(ctx):
    cfg, fl = ctx["cfg"], ctx["flops"]
    K = cfg["deployment"]["members"]
    work = sum(fl.request_flops(cfg, r.prompt_len, r.num_tokens) for r in ctx["report"].results)
    if not work:
        return None
    return 100.0 * K * work / (ctx["report"].wall_s * ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
