"""Milliseconds of backend compile from the window's open to the run's end
(the durations jax.monitoring reports; the churn cell's window keeps the
persistent cache off, so none is a load), per schema evolution applied in
that time."""


def read(ctx):
    if not ctx.evolutions_applied:
        return None
    return ctx.compile_s_in_window * 1e3 / ctx.evolutions_applied
