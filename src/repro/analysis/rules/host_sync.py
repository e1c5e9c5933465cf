"""host-sync-in-hot-path: no implicit device synchronisation inside the
async consume machinery.

The engine contract (engines.py / ops.py docstrings) is that ``dispatch``
returns UNBLOCKED jax arrays and ``emit`` is the ONE deliberate sync point
-- that asymmetry is what lets the pipeline's double-buffered async consume
overlap chunk N+1's host densification with chunk N's device execution
(PR 3), and what the device-densify path's one-transfer-per-chunk claim
rests on (PR 6).  A stray ``np.asarray``/``.block_until_ready()``/
``float(handle...)`` anywhere in ``dispatch``/``_run_async`` silently
serialises the whole overlap; one in ``emit`` is fine but must be
*annotated* so the next reader (and this rule) can tell the deliberate
sync point from an accident:

    ov = np.asarray(handle.outputs[0])[:s]  # metl: allow[host-sync-in-hot-path] the engine sync point

Starting a non-blocking copy in ``dispatch`` (``.copy_to_host_async()``,
as the engines' ``_start_readback`` does) is the intended pattern: it is no
sync, so emit's read-back waits only for the rest of a copy under way.

Scope: functions named ``dispatch`` / ``emit`` / ``_run_async`` and the
``dmm_apply*`` wrappers, in the ``repro.etl`` and ``repro.kernels``
packages -- checked with the full strict/lenient heuristics -- PLUS
(project model) every function *reachable* from a ``dispatch`` /
``dmm_apply*`` seed through the call graph, which closes the
wrapper-indirection hole: hoisting a ``np.asarray`` into an innocently
named helper called from dispatch used to hide it from this rule.
Reached helpers are checked against the EXPLICIT sync set only
(np/jax sync calls and ``.block_until_ready()``); the scalar-read
heuristics (``.item()``, ``float(x[...])``) stay name-scoped because a
general helper legitimately does host-scalar work that dispatch itself
must not.  ``_run_async`` deliberately does not seed reachability: its
callees include the whole densify subtree, whose host-numpy work is the
thing the async overlap hides.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, Sequence, Set, Tuple

from ..core import FileCtx, Finding, Rule, register
from ..project import as_project

_HOT_NAME = re.compile(r"^(dispatch|emit|_run_async|dmm_apply\w*)$")
_REACH_SEED = re.compile(r"^(dispatch|dmm_apply\w*)$")

# np-namespace calls that force a host readback of their operand
_NP_SYNC = frozenset({"asarray", "array", "ascontiguousarray", "copyto"})
# method calls that block on / read back a device array
_METHOD_SYNC = frozenset({"block_until_ready", "item", "tolist", "copy_to_host"})
# jax-namespace calls that block
_JAX_SYNC = frozenset({"device_get", "block_until_ready"})


@register
class HostSyncInHotPath(Rule):
    id = "host-sync-in-hot-path"
    title = "no implicit device sync inside dispatch/_run_async; emit's sync is annotated"
    motivation = (
        "PR 3's async double buffer and PR 6's one-transfer-per-chunk "
        "contract both die silently if a host readback sneaks into the "
        "dispatch path (the regression is invisible: results stay correct, "
        "the overlap just stops)"
    )

    def check_file(self, ctx: FileCtx) -> Iterator[Finding]:
        if not self._in_scope(ctx):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if _HOT_NAME.match(node.name):
                    yield from self._check_region(ctx, node)

    def check_project(self, ctxs: Sequence[FileCtx]) -> Iterator[Finding]:
        # helpers REACHED from dispatch/dmm_apply* (not name-matched --
        # those already ran the full heuristics in check_file): flag the
        # explicit sync calls only
        project = as_project(ctxs)
        seeds = project.seeds_matching(
            _REACH_SEED, packages=(("repro", "etl"), ("repro", "kernels"))
        )
        for qname in sorted(project.reachable(seeds)):
            info = project.functions[qname]
            if _HOT_NAME.match(info.name) or not self._in_scope(info.ctx):
                continue
            yield from self._check_explicit(info.ctx, info.node)

    @staticmethod
    def _in_scope(ctx: FileCtx) -> bool:
        return ctx.in_package("repro", "etl") or ctx.in_package("repro", "kernels")

    def _check_explicit(self, ctx: FileCtx, fn: ast.FunctionDef) -> Iterator[Finding]:
        where = f"in {fn.name}(), reachable from the dispatch path"
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            recv = f.value
            if (
                isinstance(recv, ast.Name)
                and recv.id in ("np", "numpy")
                and f.attr in _NP_SYNC
            ):
                yield ctx.finding(
                    self.id,
                    node,
                    f"np.{f.attr}() {where} forces a host readback; the "
                    "dispatch path must stay unblocked end-to-end (sync "
                    "belongs in emit, annotated "
                    "'# metl: allow[host-sync-in-hot-path] ...')",
                )
            elif (
                isinstance(recv, ast.Name)
                and recv.id == "jax"
                and f.attr in _JAX_SYNC
            ):
                yield ctx.finding(
                    self.id, node, f"jax.{f.attr}() {where} blocks on the device"
                )
            elif f.attr == "block_until_ready":
                yield ctx.finding(
                    self.id,
                    node,
                    f".block_until_ready() {where} blocks on its receiver; "
                    "keep the dispatch handle unblocked",
                )

    def _check_region(self, ctx: FileCtx, fn: ast.FunctionDef) -> Iterator[Finding]:
        where = f"in hot-path function {fn.name}()"
        # emit is post-sync host code: only the readback ENTRY points need an
        # annotation there.  dispatch/_run_async/dmm_apply* must never touch
        # device values at all, so scalar reads (.item/float(x[0])) are also
        # flagged -- in emit they are routine host-numpy work.
        strict = fn.name != "emit"
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                recv = f.value
                if (
                    isinstance(recv, ast.Name)
                    and recv.id in ("np", "numpy")
                    and f.attr in _NP_SYNC
                ):
                    yield ctx.finding(
                        self.id,
                        node,
                        f"np.{f.attr}() {where} forces a host readback; "
                        "dispatch must stay unblocked (sync belongs in emit, "
                        "annotated '# metl: allow[host-sync-in-hot-path] ...')",
                    )
                elif (
                    isinstance(recv, ast.Name)
                    and recv.id == "jax"
                    and f.attr in _JAX_SYNC
                ):
                    yield ctx.finding(
                        self.id, node, f"jax.{f.attr}() {where} blocks on the device"
                    )
                elif f.attr == "block_until_ready" or (
                    strict and f.attr in _METHOD_SYNC
                ):
                    yield ctx.finding(
                        self.id,
                        node,
                        f".{f.attr}() {where} blocks on / reads back its "
                        "receiver; keep the dispatch handle unblocked",
                    )
            elif strict and isinstance(f, ast.Name) and f.id in ("float", "int", "bool"):
                # float(x) on a python scalar is fine; float(handle.outputs[0])
                # or float(arr[0]) is a one-element device readback
                if node.args and isinstance(
                    node.args[0], (ast.Attribute, ast.Subscript)
                ):
                    target = ctx.segment(node.args[0])
                    yield ctx.finding(
                        self.id,
                        node,
                        f"{f.id}({target}) {where} is a scalar device "
                        "readback if the operand is a device handle; hoist "
                        "it out of the hot path or annotate the sync point",
                    )
