"""Device idle share of a GCN aggregation window: 100 * (1 - busy /
window), busy being the union of the device's operation intervals."""

from bench import tracing

UNIT = "%"


def read(ctx):
    if ctx.run.cell.spec["driver"] != "gcn":
        return None
    return tracing.idle_share(ctx.trace)
