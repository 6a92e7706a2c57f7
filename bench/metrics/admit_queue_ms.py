"""Mean time a request waited to be admitted: the ``queued_ms`` of the
program's ``serve.admit`` spans, from the request becoming schedulable to
the start of its admit (waits for a slot and for pages alike)."""


def read(ctx):
    queued = [args["queued_ms"] for ph, name, _c, _ts, _d, args in ctx["events"]
              if ph == "X" and name == "serve.admit" and "queued_ms" in args]
    return sum(queued) / len(queued) if queued else None
