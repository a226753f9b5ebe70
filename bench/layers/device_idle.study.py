"""Idle share of the chips over the traced window (device trace)."""

from bench.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
