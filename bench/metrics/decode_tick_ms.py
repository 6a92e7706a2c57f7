"""Mean wall time of the engine's decode tick, from the program's
``serve.decode_tick`` spans (dispatch to the host's read of the emitted
tokens, so the device's work is inside)."""


def read(ctx):
    ticks = [dur for ph, name, _c, _ts, dur, _a in ctx["events"]
             if ph == "X" and name == "serve.decode_tick"]
    return sum(ticks) / len(ticks) / 1e6 if ticks else None
