"""Share of the window in which no operation runs on the device: 1 −
union of the operation intervals over the window, mean over devices.
Layer: device.  Moves ``updates_per_s``."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * ctx.trace.idle_share()
