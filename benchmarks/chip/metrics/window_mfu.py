"""Device: the whole served step's share (%) of the peak: the operations of
the work served in the traced window (each share's prefill and the decode
steps whose tokens it returns) over the traced window's seconds times the
cell's chips times the bf16 peak."""
import counts


def read(ctx):
    if ctx.peaks is None:          # no chip, no peak
        return None
    t = ctx.trace
    if t is None or not ctx.traced or t.window_s <= 0:
        return None
    flops = 0.0
    for r in ctx.traced:
        for s in r.shares:
            z = counts.sizes(ctx.doc, s.level)
            flops += counts.prefill_flops(z, s.served, ctx.prompt_len)
            flops += sum(counts.decode_flops(z, s.served,
                                             ctx.prompt_len + 1 + i)
                         for i in range(ctx.decode_steps - 1))
    return 100.0 * flops / (t.window_s * ctx.chips * ctx.peaks.bf16_flops)
