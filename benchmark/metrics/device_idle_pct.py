"""Share of the traced span in which no op ran on the device: 1 minus the
union of device-op intervals over the span, from the profiler trace, %.
Layer: device (one v5e)."""

from harness import layers


def read(ctx):
    return layers.idle_pct(ctx)
