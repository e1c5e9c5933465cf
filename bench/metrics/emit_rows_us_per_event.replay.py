"""Host time in ``emit.rows``, the program's span around ``_emit_rows`` (one
tuple per output row), per event written in the window."""

from bench.lib import program_spans as ps


def read(ctx):
    return ps.us_per_event(ps.window(ctx), "emit.rows", ctx.events_in_window)
