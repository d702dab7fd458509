"""Share runner: seconds per request spent building a level's weights on a
device because another level was resident (the runner's ``build_s``),
mean over the window's requests."""


def read(ctx):
    if not ctx.records:
        return None
    return sum(s.build_s for r in ctx.records for s in r.shares) / len(
        ctx.records)
