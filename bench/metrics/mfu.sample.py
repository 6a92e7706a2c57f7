"""Model FLOP utilisation of sampling: forward and backward FLOPs per
gradient token (``flops.py``; recomputation not counted) times the tokens
of the window, over the window times the chips' peak."""


def read(ctx):
    cfg, mix = ctx["cfg"], ctx["mix"]
    work = ctx["flops"].train_flops_per_token(cfg, mix["seq"]) * ctx["tokens"]
    return 100.0 * work / (ctx["window_s"] * ctx["chips"] * ctx["peak"]["bf16_flops_per_s"])
