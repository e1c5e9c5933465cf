"""Events per poll of the benchmark's consumer, over the window's polls."""


def read(ctx):
    if ctx.poll_sizes.size == 0:
        return None
    return float(ctx.poll_sizes.mean())
