#!/usr/bin/env bash
# Full-suite gate + perf smoke.  Run from anywhere:
#
#     bash scripts/ci.sh
#
# 1. the static invariant analyzer (python -m repro.analysis) over
#    src/benchmarks/examples: thirteen rules on a whole-program project
#    model (src/repro/analysis/project.py: import-aware symbol
#    resolution, an approximate call graph, hot-path reachability, and a
#    donate_argnums dataflow map).  The per-file rules -- private-reach-in
#    (no private METLApp/engine/Registry access outside repro.etl /
#    repro.core, alias- and import-aware), host-sync-in-hot-path
#    (dispatch and everything it reaches stays unblocked; emit's sync
#    points are annotated), hot-path-python-loop (no per-event
#    loops/payload walks anywhere reachable from densify/dispatch/
#    consume), control-plane-purity (mutate() only in
#    StateCoordinator.apply or its private helpers; frozen
#    ControlEvents), jit-cache-hygiene, kernel-ref-parity -- plus the
#    cross-module rules: donated-buffer-reuse (no read of a buffer after
#    it is donated to a jit program; donation is a no-op on CPU CI, so
#    only this gate sees the TPU/GPU corruption), single-writer-control
#    (only StateCoordinator.apply writes control_log/coordinator state),
#    epoch-pin-escape (DenseChunk/ColumnarDense always carry their plan
#    pin; no plan read through a chunk across a coordinator mutation),
#    transfer-accounting (host->device conversions on the per-chunk path
#    only at the accounted _to_device site), plan-publish-single-site
#    (only repro.etl.plan / repro.core.dmm_jax may call the fused-plan
#    builders or cut a PlanPublished event -- every other layer acquires
#    epoch leases through PlanManager), and the waiver audits
#    (bad-waiver, unused-waiver).  Findings render as ::error GitHub
#    annotations in CI logs; the JSON report is written next to the bench
#    artifact (ANALYSIS.json).  Waivers are inline '# metl:
#    allow[rule-id] reason' comments; a reasonless or stale waiver fails
#    the gate.  A second, scoped sweep covers tests/ (private-reach-in +
#    waiver audits: test files may exercise internals via their own
#    waived shim lines but not silently grow private couplings);
# 2. a mypy pass (mypy.ini: repro.etl + repro.core + repro.kernels +
#    repro.analysis, basic strictness; version pinned in
#    requirements-dev.txt) when mypy is importable; skipped with a notice
#    on the bare jax container;
# 3. the FULL test suite with zero tolerated failures -- includes the
#    tier-1 set (ROADMAP.md), the multi-device subprocess tests, the
#    sharded-vs-replicated fused-consume parity tests, and the analyzer's
#    own suite (tests/test_analysis.py, incl. the repo self-check);
# 4. the streaming-pipeline example (two sinks, async double-buffered
#    consume) as an end-to-end smoke of the Pipeline API;
# 5. the mid-stream schema-evolution example: typed control events riding
#    the stream in-band (SchemaEvolved + a Freeze/Thaw window with a
#    deferred evolution + VersionDeleted), applied at chunk boundaries by
#    the single-writer coordinator, with the control-log replay
#    determinism check (the script asserts state + DPM bit-exactness);
# 5b. the multi-process replication smoke
#    (scripts/replication_smoke.py): a 1-leader + 2-follower cluster over
#    real sockets splits the chunk grid under churn and a Freeze/Thaw
#    window; the leader is killed mid-stream by fault injection (after
#    emitting a chunk, before checkpointing it), a new leader resumes
#    from the atomic (control_log offset, source offset) checkpoint under
#    the next term, and the merged output must match the single-process
#    oracle bit-for-bit -- zero dropped, zero duplicated rows;
# 6. a tiny-shape run of the mapping + compaction + replication
#    benchmarks so the
#    fused- and sharded-engine perf paths (kernel, shard_map dispatcher,
#    consume, sync-vs-async pipeline, columnar + device densify) and the
#    epoched plan lifecycle can't rot silently even when no test exercises
#    the timing harness.  bench_mapping itself exits non-zero -- failing
#    this gate -- if the fused engine's dispatches-per-chunk regress above
#    1 (direct consume, device densify, async pipeline, or any cluster
#    instance across the epoch-transition A/B), if device densify makes
#    more than ONE host->device transfer per chunk, if the columnar
#    densify is SLOWER than the legacy dict walk at the bench's default
#    chunk size, if any densify path (columnar, device, sharded-device,
#    pipelined-device) diverges bit-wise from its host oracle, or if the
#    epoch transition drops/duplicates rows (in-band vs out-of-band
#    oracle, 4-instance cluster vs single instance).  bench_replication
#    gates in-process control-plane parity (replayed replica and
#    promoted-on-failover replica bit-equal to the leader; leader +
#    follower data split matching the oracle row-for-row) while writing
#    replication lag and failover time into the artifact.  bench_compaction
#    gates the PlanManager soak: incremental recompaction must emit
#    row-keys identical to the full-rebuild oracle across every churn
#    cutover, and (full size only) the amortised incremental rebuild time
#    and p99 chunk latency
#    must beat/track the full-rebuild baseline.  The run goes through
#    benchmarks/run.py --artifact, which writes a BENCH_<ts>.json
#    trajectory artifact;
# 7. the perf-trajectory diff: scripts/perf_diff.py compares the fresh
#    artifact's events/s metrics against the last comparable artifact
#    checked in under benchmarks/trajectory/ and fails on a >20% drop
#    (tolerance overridable via PERF_TOL);
# 8. the ETL roofline over the fresh artifact: every engine configuration
#    (fused host-densify, fused device-densify, sharded both ways) priced
#    on the transfer/memory/launch walls on one chart.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# created up front so the analyzer's JSON report lands next to the bench
# artifact written in step 6
BENCH_DIR="$(mktemp -d)"
trap 'rm -rf "$BENCH_DIR"' EXIT

echo "== static invariant analyzer (repro.analysis, project model) =="
# --output github: findings render as ::error annotations (no-ops in a
# plain terminal, overlaid on the diff under GitHub Actions); --report
# keeps the machine-readable JSON next to the bench artifact either way
python -m repro.analysis src benchmarks examples \
    --output github --report "$BENCH_DIR/ANALYSIS.json"

echo "== analyzer: tests/ sweep (private-reach-in + waiver audits) =="
# scoped: test files deliberately poke internals through waived shim
# lines, but new private couplings and stale waivers must not creep in
python -m repro.analysis tests \
    --select private-reach-in,bad-waiver,unused-waiver --output github

echo "== mypy (etl + core + kernels + analysis, mypy.ini) =="
if python -c "import mypy" 2>/dev/null; then
  python -m mypy --config-file mypy.ini \
      src/repro/etl src/repro/core src/repro/kernels src/repro/analysis
else
  echo "skipped: mypy not installed (pip install -r requirements-dev.txt)"
fi

echo "== full suite (tier-1 + distributed + sharded parity; 0 failures) =="
python -m pytest -q

echo "== pipeline example (two sinks, async double-buffered consume) =="
python examples/pipeline_stream.py --chunks 4 --prompts 500

echo "== mid-stream schema evolution (in-band control + log replay) =="
python examples/schema_evolution.py --steps 4

echo "== replication smoke (leader kill + failover, exactly-once rows) =="
python scripts/replication_smoke.py --fast

echo "== benchmark smoke (engines, device densify, pipeline, plan soak) =="
python -m benchmarks.run --only mapping,compaction,replication --smoke --artifact "$BENCH_DIR"

echo "== perf trajectory diff (vs benchmarks/trajectory, >20% drop fails) =="
python scripts/perf_diff.py "$BENCH_DIR" --baseline benchmarks/trajectory

echo "== ETL roofline (engine configs from the smoke artifact) =="
python -m repro.launch.roofline --etl "$BENCH_DIR"/BENCH_*.json
