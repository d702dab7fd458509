"""Engine: the prefill's share (%) of the chip's bf16 peak: the operations
the prefills of the window need (``counts.prefill_flops``) over the
runner's prefill seconds, each share on one chip."""
import counts


def read(ctx):
    if ctx.peaks is None:          # no chip, no peak
        return None
    shares = [s for r in ctx.records for s in r.shares]
    secs = sum(s.prefill_s for s in shares)
    if not shares or secs <= 0:
        return None
    flops = sum(counts.prefill_flops(counts.sizes(ctx.doc, s.level),
                                     s.served, ctx.prompt_len)
                for s in shares)
    return 100.0 * flops / secs / ctx.peaks.bf16_flops
