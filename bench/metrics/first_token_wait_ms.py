"""Mean wait for a request's first token after its admit returned: the
``wait_ms`` of the program's ``serve.first_token`` instants, from the end of
the ``serve.admit`` span to the host holding the token (the prefill's device
time, less what ran while the admit dispatched)."""


def read(ctx):
    waits = [args["wait_ms"] for ph, name, _c, _ts, _d, args in ctx["events"]
             if ph == "i" and name == "serve.first_token"]
    return sum(waits) / len(waits) if waits else None
