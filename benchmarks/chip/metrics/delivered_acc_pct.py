"""Gateway: items-weighted accuracy (%) of the levels the shares were served
at, over all requests of the window, from the configuration file's
ladder. A guard: it falls when plans approximate more; ``acc_shortfall``
in ``correct`` holds each request to its own ``acc_req``."""


def read(ctx):
    acc = [lv["accuracy"] for lv in ctx.doc["ladder"]]
    shares = [s for r in ctx.records for s in r.shares]
    items = sum(s.items for s in shares)
    if not items:
        return None
    return sum(s.items * acc[s.level] for s in shares) / items
