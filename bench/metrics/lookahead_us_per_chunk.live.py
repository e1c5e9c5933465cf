"""Mean time of ``pipeline.lookahead``, the program's span from chunk N's
dispatch to its emit (the poll, triage and densify of chunk N+1), over the
window's chunks: the hold the double buffer adds to every row of N."""

from bench.lib import program_spans as ps


def read(ctx):
    return ps.mean_us(ps.window(ctx), "pipeline.lookahead")
