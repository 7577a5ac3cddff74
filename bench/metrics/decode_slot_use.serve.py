"""Share of the lockstep decode slots that served a token: each request's
own new tokens over rows x decode steps, summed over the window's
batches. The steps are the program's ``serve.step`` spans inside each
``serve.batch`` span; the rows and new tokens come from the driver's
record of the requests it handed to each ``Server.serve`` call, one batch
each.

While every row of a batch steps until the batch's longest answer, the
traffic file alone fixes the value (43.75% for danube.chat's answers of
32 and 128 tokens); it can move only once batching is continuous."""

from bench import program_trace

UNIT = "%"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "serve":
        return None
    batches = program_trace.spans(ctx.trace, "serve.batch")
    if not batches:
        return None     # a program without the spans
    served = ctx.record.served
    if len(batches) != len(served):
        raise ValueError(f"{len(batches)} serve.batch spans for "
                         f"{len(served)} served calls in the window")
    steps = program_trace.spans(ctx.trace, "serve.step")
    slots = sum(len(s.prompts) * len(program_trace.inside(steps, b))
                for s, b in zip(served, batches))
    return 100.0 * sum(sum(s.new_tokens) for s in served) / slots
