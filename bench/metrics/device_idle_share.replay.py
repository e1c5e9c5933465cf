"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    red = ctx.reduction
    if red is None:
        return None
    return red.idle_share * 100.0
