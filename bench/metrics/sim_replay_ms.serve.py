"""Host time of the modeled-memory replay after each ``serve`` call: the
mean length of the program's ``serve.model_memory`` spans in the window
(``Server.model_memory``: the KV access stream through
``MemoryController.simulate``), in which the device has nothing
queued."""

from bench import program_trace

UNIT = "ms"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "serve":
        return None
    spans = program_trace.spans(ctx.trace, "serve.model_memory")
    if not spans:
        return None     # a program without the spans
    return sum(b - a for a, b in spans) / len(spans) / 1e6
