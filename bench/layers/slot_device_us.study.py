"""Device busy microseconds per scan slot of the engine (device trace)."""

from bench.readers import slot_device_us


def read(ctx):
    return slot_device_us(ctx)
