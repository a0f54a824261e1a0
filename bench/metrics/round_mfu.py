"""The whole round's share of the chips' peak: the FLOPs a round requires
(from shapes, by the app adapter; nothing recomputed counts) times the
rounds of the traced window, over the window's length in the device
trace, over chips × bf16 peak.  Silent where a device stopped recording
inside the window, since the rounds it holds are then not whole.  Layer:
device.  Moves ``updates_per_s``."""


def read(ctx):
    tr = ctx.trace
    if (ctx.peaks is None or ctx.rounds == 0 or tr is None or not tr.ops
            or tr.dropped_at is not None):
        return None
    rate = ctx.job.round_flops() * ctx.rounds / tr.window_s
    return 100.0 * rate / (ctx.chips * float(ctx.peaks["bf16_flops_per_s"]))
