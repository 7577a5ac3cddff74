"""Device time of one decode step: the decode program's executions in the
window (``Server._decode``, XLA module ``jit_decode_step``), over their
count."""

from bench import tracing

UNIT = "ms"
MODULE = "jit_decode_step"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "serve":
        return None
    seconds, n = tracing.module_time(ctx.trace, MODULE)
    return 1e3 * seconds / n if n else None
