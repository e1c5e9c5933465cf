"""XLA executables built or loaded inside the window (jax.monitoring's
backend-compile event, which a persistent-cache load also fires)."""


def read(ctx):
    return float(ctx.compiles_in_window)
