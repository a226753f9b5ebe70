"""Host-clock microseconds the program waited for its device work per
scan slot (program spans): the window's ``fg.sweep.pull`` time over its
scan slots. A check on ``slot_device_us.study``, which reads the device
trace."""

from bench.span_calls import pull_ns, window_calls


def read(ctx):
    calls = window_calls(ctx)
    if calls is None:
        return None
    return sum(pull_ns(t) for _, t in calls) * 1e-3 / ctx.slots
