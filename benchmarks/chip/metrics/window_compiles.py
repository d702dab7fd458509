"""Engine: programs compiled or loaded per request inside the window (the
runner's ``compiles`` counter, summed over the window's shares), mean over
its requests: 0 when warm-up covered every shape. None where the program
keeps no such counter."""


def read(ctx):
    shares = [s for r in ctx.records for s in r.shares]
    if not shares or not all(hasattr(s, "compiles") for s in shares):
        return None
    return sum(s.compiles for s in shares) / len(ctx.records)
