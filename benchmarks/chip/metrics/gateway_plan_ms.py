"""Gateway: host milliseconds per request in ``gn.handle`` (admission and
Algorithm 1's plan), mean over the window's requests."""


def read(ctx):
    if not ctx.records:
        return None
    return 1e3 * sum(r.plan_s for r in ctx.records) / len(ctx.records)
