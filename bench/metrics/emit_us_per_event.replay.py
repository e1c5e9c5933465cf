"""Host time in the engine's ``emit`` (the device sync and the row build)
per event written in the window."""


def read(ctx):
    if not ctx.events_in_window:
        return None
    return ctx.spans.total("emit", ctx.t0, ctx.tw) / ctx.events_in_window * 1e6
