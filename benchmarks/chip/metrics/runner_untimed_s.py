"""Share runner: seconds per request inside ``runner.run`` outside the
runner's own timed parts (weight build, prefill, decode steps): compiles,
prompt generation, host copies. Mean over the window's requests."""


def read(ctx):
    if not ctx.records:
        return None
    k = ctx.decode_steps
    untimed = [r.run_s - sum(s.build_s + s.prefill_s + k * s.decode_step_s
                             for s in r.shares) for r in ctx.records]
    return sum(untimed) / len(untimed)
