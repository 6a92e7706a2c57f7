"""Host time of the engine's decode tick: the mean over ticks of its
``serve.tick.dispatch`` span (key fold, page growth, table uploads and the
dispatch) plus its ``serve.tick.collect`` span (the tokens' append and the
finished requests' retirement), from the program's spans."""


def read(ctx):
    dispatch = [dur for ph, name, _c, _ts, dur, _a in ctx["events"]
                if ph == "X" and name == "serve.tick.dispatch"]
    collect = [dur for ph, name, _c, _ts, dur, _a in ctx["events"]
               if ph == "X" and name == "serve.tick.collect"]
    if not dispatch or not collect:
        return None
    return (sum(dispatch) + sum(collect)) / len(dispatch) / 1e6
