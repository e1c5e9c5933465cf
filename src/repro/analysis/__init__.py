"""repro.analysis -- AST-based invariant analyzer for the METL repo.

Replaces ci.sh's two ``git grep`` encapsulation gates with a real static
analyzer: each rule encodes an invariant a past PR fought for, so that the
regression class it names fails CI instead of review.  Run it as::

    python -m repro.analysis src benchmarks examples
    python -m repro.analysis --list-rules
    python -m repro.analysis src --select private-reach-in --output json

Exit codes: 0 clean, 1 findings, 2 usage/internal error.

The rule catalog (see docs/analysis.md for the long-form version):

``private-reach-in``
    No private METLApp/engine/Registry attribute access outside the owning
    package (``repro.etl`` / ``repro.core``).  AST successor of the two
    grep gates: tracks aliases (``shadow = app; shadow._fused``), ignores
    strings/comments, and keeps the known private names as an any-receiver
    backstop.  Motivation: PR 3/PR 5 moved launchers and benchmarks onto
    the public engine protocol.

``host-sync-in-hot-path``
    ``dispatch``/``_run_async``/``dmm_apply*`` must never read back or
    block on device values; ``emit`` is the one sync point and its
    readbacks must carry a waiver comment.  Motivation: PR 3's async
    double buffer and PR 6's one-transfer-per-chunk contract die silently
    when a stray ``np.asarray`` lands in the dispatch path.

``hot-path-python-loop``
    No per-event python loops or ``.payload()`` dict walks inside
    densify/dispatch functions; per-column/per-shard loops are fine.
    Motivation: the PR-1 and PR-4 regression class (8.5x densify
    throughput once vectorised).

``control-plane-purity``
    ``ControlEvent.mutate()`` is callable only from
    ``StateCoordinator.apply`` (the single writer that logs events for
    replay), and every ControlEvent subclass must be a frozen dataclass.
    Motivation: PR 5's bit-exact control_log replay.

``jit-cache-hygiene``
    ``lru_cache``-wrapped jit program builders (kernels/ops.py) must take
    only annotated, hashable static parameters; no ``*args``, no array
    annotations, no unhashable literals at call sites.  Motivation: a
    churning cache key recompiles every chunk without failing anything.

``kernel-ref-parity``
    Every Pallas kernel in ``kernels/`` has a pure-jnp twin in
    ``kernels/ref.py`` and a test that references both the kernel and its
    twin.  Motivation: the onehot test compared against the wrong twin.

The cross-module rules ride the whole-program model in
:mod:`repro.analysis.project` (import-aware symbol resolution, an
approximate call graph, hot-path reachability, and a ``donate_argnums``
dataflow map, built ONCE per run):

``donated-buffer-reuse``
    No read of a buffer after it was passed in a donated position of a
    jitted program.  Motivation: ``donate_argnums`` is a no-op on the CPU
    CI backend -- a reuse passes every test and corrupts on TPU/GPU
    (PR 6's device-densify contract).

``single-writer-control``
    Only ``StateCoordinator.apply`` (resolved through wrappers via the
    call graph) may append to ``control_log`` or mutate coordinator
    state.  Motivation: PR 5's bit-exact control-log replay has exactly
    one writer.

``epoch-pin-escape``
    Every ``DenseChunk``/``ColumnarDense`` construction carries its
    ``plan=`` epoch pin, and no ``.plan`` read through a chunk crosses a
    coordinator mutation in the same scope.  Motivation: PR 5's
    epoch-transition contract -- an unpinned in-flight chunk maps rows
    with the wrong epoch's plan.

``transfer-accounting``
    No host->device conversion reachable from the per-chunk dispatch
    path outside the single waived ``_to_device`` site in ``engines.py``.
    Motivation: PR 6's one-transfer-per-chunk contract, enforced by
    reachability instead of by whichever configurations the bench runs.

``plan-publish-single-site``
    Only ``repro.etl.plan`` (the PlanManager) and ``repro.core.dmm_jax``
    (the lowering layer) may call the fused-plan builders
    (``compile_fused`` / ``compile_fused_sharded`` / ``recompile_columns``
    / ``splice_fused``), construct ``FusedDMM``/``ShardedFusedDMM``, or
    cut a ``PlanPublished`` event; ``compile_dpm`` stays free.
    Motivation: PR 9's epoch counter, rebuild accounting, the
    incremental-vs-full oracle and PlanPublished replay all hang off one
    build path -- a
    hand-built plan is an unmanaged epoch that dodges every contract.

Waivers: append ``# metl: allow[rule-id] reason`` to the offending line
(or the line above as a standalone comment; on a ``def`` line it covers
the whole function).  The reason is mandatory -- a reasonless waiver or an
unknown rule id is itself a finding (``bad-waiver``), a well-formed
waiver that suppresses nothing is ``unused-waiver``, and neither audit
finding can be waived.
"""

from .core import (  # noqa: F401
    Finding,
    FileCtx,
    Report,
    Rule,
    RULES,
    Waiver,
    analyze,
    collect_files,
    register,
)
from .project import Project, as_project  # noqa: F401

__all__ = [
    "Finding",
    "FileCtx",
    "Project",
    "Report",
    "Rule",
    "RULES",
    "Waiver",
    "analyze",
    "as_project",
    "collect_files",
    "register",
]
