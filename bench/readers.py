"""Shared arithmetic of the per-layer readers in ``bench/layers/``.

A reader returns ``None`` where it finds nothing to read (no traced call,
no event of its kernel), and the harness then leaves its metric out.
"""

from __future__ import annotations

from bench import costs


def idle_pct(ctx):
    """Share of the traced window in which no operation ran, averaged
    over the chips."""
    return 100.0 * (1.0 - ctx.trace.mean_busy_s / ctx.trace.window_s)


def slot_device_us(ctx):
    """Device busy time per scan slot (all runs of a call advance one
    slot per scan step), per chip."""
    if ctx.slots == 0:
        return None
    return 1e6 * ctx.trace.mean_busy_s / ctx.slots


def kernel_share(ctx, names):
    """The kernel's device time over the chips' busy time."""
    k = ctx.trace.kernel_s(names)
    if k == 0.0:
        return None
    return 100.0 * k / (ctx.trace.mean_busy_s * ctx.trace.n_devices)


def kernel_roofline(ctx, names, ops_bytes):
    """The least time the chip could take for the kernel's work over its
    device time. ``ops_bytes`` is one run's one call (one slot); the work
    is that times the run-slots executed in the window."""
    k = ctx.trace.kernel_s(names)
    if k == 0.0 or ctx.run_slots == 0:
        return None
    ops, nbytes = ops_bytes
    return costs.roofline_share(ops * ctx.run_slots, nbytes * ctx.run_slots,
                                k, ctx.device_kind)
