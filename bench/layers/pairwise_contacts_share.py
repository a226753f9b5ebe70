"""Share of the chips' busy time spent in the dense contact kernel."""

from bench.readers import kernel_share

NAMES = ("pairwise_contacts",)


def read(ctx):
    return kernel_share(ctx, NAMES)
