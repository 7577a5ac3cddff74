"""Share of the compute roofline that prefill reaches: the operations the
window's prompts need (``counts.tokens_flops`` over each prompt's own
tokens, without its left padding, plus one LM head per request for its
first token) at the chip's peak bf16 rate, over the prefill programs'
device time in the window (``Server._prefill``, a jitted lambda: XLA
module ``jit__lambda``). The padding and the extra work of a chunked
state-space scan are not counted, so the share stays under 100%."""

from bench import counts, tracing

UNIT = "%"
MODULE = "jit__lambda"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "serve" or ctx.peaks is None:
        return None
    seconds, n = tracing.module_time(ctx.trace, MODULE)
    if not n:
        return None
    cfg = ctx.record.cfg
    flops = sum(counts.tokens_flops(cfg, 1, len(p)) + counts.head_flops(cfg)
                for s in ctx.record.served for p in s.prompts)
    return 100.0 * flops / ctx.peaks["bf16_flops_per_s"] / seconds
