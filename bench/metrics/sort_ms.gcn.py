"""Device time of the request sorts per GCN step: the ``sort`` operations
(the scheduler's stable sort and its inverse in
``scheduler.sort_requests``) inside the step program ``jit_gcn_step``,
over the step's executions."""

from bench import tracing

UNIT = "ms"
MODULE = "jit_gcn_step"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "gcn":
        return None
    _, n = tracing.module_time(ctx.trace, MODULE)
    if not n:
        return None
    return 1e3 * tracing.op_time(ctx.trace, r"sort", within=MODULE) / n
