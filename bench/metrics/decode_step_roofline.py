"""Share of the HBM roofline that the decode step reaches: the bytes a
step needs (``counts.decode_step_bytes``: every weight once, the
embedding rows looked up, and the cached state of each request that still
needs the step, over its own valid context) at the chip's peak
bandwidth, over the step's measured device time."""

from bench import counts, tracing

UNIT = "%"
MODULE = "jit_decode_step"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "serve" or ctx.peaks is None:
        return None
    seconds, n = tracing.module_time(ctx.trace, MODULE)
    if not n:
        return None
    cfg = ctx.record.cfg
    needed = sum(sum(counts.decode_step_bytes(
        cfg, [(len(p), m) for p, m in zip(s.prompts, s.new_tokens)],
        max(s.new_tokens))) for s in ctx.record.served)
    steps = sum(max(s.new_tokens) for s in ctx.record.served)
    if steps != n:
        raise ValueError(f"{n} decode executions traced, {steps} expected")
    return 100.0 * needed / ctx.peaks["hbm_bytes_per_s"] / seconds
