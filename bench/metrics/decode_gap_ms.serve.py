"""Host gap per decode step: the time inside the program's ``serve.step``
spans (the token reads of one step and the dispatch of the next) in
which no operation ran on the device, over the number of those spans.
The spans must match the window's decode executions (XLA module
``jit_decode_step``) one for one."""

from bench import program_trace, tracing

UNIT = "ms"
MODULE = "jit_decode_step"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "serve":
        return None
    steps = program_trace.spans(ctx.trace, "serve.step")
    if not steps or not tracing.device_planes(ctx.trace):
        return None     # a program without the spans, or no device
    _, n = tracing.module_time(ctx.trace, MODULE)
    if n != len(steps):
        raise ValueError(f"{len(steps)} serve.step spans but {n} "
                         f"{MODULE} executions in the window")
    return program_trace.idle_ns(ctx.trace, steps) / len(steps) / 1e6
