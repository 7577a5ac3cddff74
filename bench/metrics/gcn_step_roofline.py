"""Share of the HBM roofline that the GCN step reaches: the bytes its
blocks need (``counts.gcn_block_bytes``: distinct source rows read once,
the ids, distinct destination rows written once) at the chip's peak
bandwidth, over the step program's measured device time."""

from bench import counts, tracing

UNIT = "%"
MODULE = "jit_gcn_step"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "gcn" or ctx.peaks is None:
        return None
    seconds, n = tracing.module_time(ctx.trace, MODULE)
    rec = ctx.record
    if not n or not rec.distinct:
        return None
    if n != rec.steps:
        raise ValueError(f"{n} step executions traced, {rec.steps} run")
    block = ctx.run.cell.traffic["block_edges"]
    needed = sum(counts.gcn_block_bytes(ctx.run.cell.config, block,
                                        *rec.distinct[i % rec.walk])
                 for i in range(rec.steps))
    return 100.0 * needed / ctx.peaks["hbm_bytes_per_s"] / seconds
