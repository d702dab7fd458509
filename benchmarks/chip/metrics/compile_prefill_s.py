"""Engine: seconds per request in the compile step's extra prefill (the
runner's ``engine.compile_prefill`` spans), summed over the window's shares,
mean over its requests. None where the program records no spans."""


def read(ctx):
    shares = [s for r in ctx.records for s in r.shares]
    if not shares or not all(getattr(s, "spans", ()) for s in shares):
        return None
    ns = sum(sp.end_ns - sp.start_ns for s in shares for sp in s.spans
             if sp.name == "engine.compile_prefill")
    return ns * 1e-9 / len(ctx.records)
