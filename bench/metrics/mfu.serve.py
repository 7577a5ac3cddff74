"""Model FLOP utilisation of the serving window: the operations that the
window's requests need (``counts.request_flops``: their own prompts
without padding, attention over valid context, one LM head per new
token) over the traced window times the chip's peak."""

from bench import counts, tracing

UNIT = "%"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "serve" or ctx.peaks is None:
        return None
    cfg = ctx.record.cfg
    flops = sum(counts.request_flops(cfg, len(p), m)
                for s in ctx.record.served
                for p, m in zip(s.prompts, s.new_tokens))
    t0, t1 = tracing.window(ctx.trace)
    return 100.0 * flops / ((t1 - t0) / 1e9) / ctx.peaks["bf16_flops_per_s"]
