"""Host time in ``emit.sync``, the program's span around the device sync and
the read-back of a chunk's output in ``FusedEngine.emit``, per event written
in the window."""

from bench.lib import program_spans as ps


def read(ctx):
    return ps.us_per_event(ps.window(ctx), "emit.sync", ctx.events_in_window)
