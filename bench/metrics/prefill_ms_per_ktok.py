"""Device time of prefill per thousand prompt tokens the program ran,
left padding included: the prefill programs' time in the window
(``Server._prefill``, a jitted lambda: XLA module ``jit__lambda``) over
batch x padded prompt length of every batch served."""

from bench import tracing

UNIT = "ms/ktok"
MODULE = "jit__lambda"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "serve":
        return None
    seconds, n = tracing.module_time(ctx.trace, MODULE)
    tokens = sum(len(s.prompts) * s.width for s in ctx.record.served)
    return 1e3 * seconds / (tokens / 1e3) if n else None
