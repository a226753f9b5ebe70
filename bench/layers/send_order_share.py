"""Share of the chips' busy time spent in the sorts of the any-M delivery
path: the double argsort that ranks each connection's send order
(``fg.deliveries.order``). The M = 1 step holds no sort, so the metric
reads nothing there."""

from bench.readers import kernel_share

NAMES = ("sort",)


def read(ctx):
    return kernel_share(ctx, NAMES)
