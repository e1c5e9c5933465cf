"""Device time of the uid resolve per chunk: the operations of the mapping
program (``jit_metl_map_chunk``) that ran under the ``uid_resolve`` named
scope in the traced window, over the number of ``densify_map`` launches,
one per chunk (``bench/lib/scopes.py``)."""

from bench.lib import scopes

MODULE, SCOPE = "jit_metl_map_chunk", "uid_resolve"


def read(ctx):
    red = ctx.reduction
    if red is None or not red.kernel_events:
        return None
    trace = scopes.load(scopes.window_open_wall(ctx.t0))
    if trace is None or trace.open_ns is None:
        return None
    ns, n = scopes.scoped_ns(trace.ops, trace.open_ns, trace.open_ns + red.window_ns,
                             MODULE, SCOPE)
    if not n:
        return None
    return ns / red.kernel_events / 1e3
