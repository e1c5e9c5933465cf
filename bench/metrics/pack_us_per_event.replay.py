"""Host time in ``densify.pack``, the program's span around
``_pack_columnar`` (the chunk's one int32 buffer), per event written in the
window."""

from bench.lib import program_spans as ps


def read(ctx):
    return ps.us_per_event(ps.window(ctx), "densify.pack", ctx.events_in_window)
