"""Programs compiled inside the timed window; 0 when every chunk reuses
the warmed program.  Layer: executor.  Moves ``updates_per_s``."""


def read(ctx):
    return ctx.recompiles_in_window
