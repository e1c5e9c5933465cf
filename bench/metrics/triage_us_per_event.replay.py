"""Host time in ``METLApp.triage`` per event written in the window."""


def read(ctx):
    if not ctx.events_in_window:
        return None
    return ctx.spans.total("triage", ctx.t0, ctx.tw) / ctx.events_in_window * 1e6
