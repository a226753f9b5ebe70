"""Roofline share of the row-merge kernel of the learning layer (device
trace). The trace names the kernel's instruction after its jitted wrapper,
``_rows_pallas``; ``gossip_merge_rows`` is the name a ``name=`` on its
``pallas_call`` would give."""

from bench import costs
from bench.readers import kernel_roofline

NAMES = ("_rows_pallas", "gossip_merge_rows")


def read(ctx):
    learn = ctx.traffic["learn"]
    dim = learn["n_features"] * learn["n_classes"] + learn["n_classes"]
    return kernel_roofline(
        ctx, NAMES, costs.gossip_merge_rows(ctx.config["n_nodes"], dim))
