"""Roofline share of the dense contact kernel (device trace).

The trace names the kernel's instruction after its jitted wrapper,
``pairwise_contacts``; a ``name=`` on its ``pallas_call`` would give that
name too."""

from bench import costs
from bench.readers import kernel_roofline

NAMES = ("pairwise_contacts",)


def read(ctx):
    return kernel_roofline(ctx, NAMES,
                           costs.pairwise_contacts(ctx.config["n_nodes"]))
