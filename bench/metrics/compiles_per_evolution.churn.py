"""Programs XLA compiled from the window's open to the run's end, per
schema evolution applied in that time.  Persistent-cache loads do not
count; the churn cell's window keeps that cache off, so it has none."""


def read(ctx):
    if not ctx.evolutions_applied:
        return None
    return (ctx.compiles_in_window - ctx.loads_in_window) / ctx.evolutions_applied
