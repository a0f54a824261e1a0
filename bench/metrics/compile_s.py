"""Seconds of backend compilation during set-up (``jax.monitoring``
``backend_compile_duration``; a persistent-cache read reports as one
too).  Layer: set-up.  Moves ``setup_s``."""


def read(ctx):
    return ctx.compile_setup_s
