"""Engine: the decode step's share (%) of its roofline: the least time of a
step (the larger of its operations over the bf16 peak and its bytes over
HBM bandwidth, ``counts.decode_bound_s``) over the runner's measured step
seconds, summed over the window's shares. Which bound binds is printed."""
import sys

import counts


def read(ctx):
    if ctx.peaks is None:          # no chip, no peak
        return None
    bound, secs, kinds = 0.0, 0.0, set()
    for r in ctx.records:
        for s in r.shares:
            c = counts.of(ctx.doc, s.level)
            steps = [counts.decode_bound_s(
                c, s.served, ctx.prompt_len + 1 + i, ctx.peaks.bf16_flops,
                ctx.peaks.hbm_bytes_per_s) for i in range(ctx.decode_steps)]
            bound += sum(b["s"] for b in steps) / len(steps)
            kinds.update(b["bound"] for b in steps)
            secs += s.decode_step_s
    if secs <= 0:
        return None
    print(f"decode_roofline: bound by {'/'.join(sorted(kinds))}",
          file=sys.stderr)
    return 100.0 * bound / secs
