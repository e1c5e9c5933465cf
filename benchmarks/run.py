"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME[,NAME...]] \
        [--smoke] [--artifact DIR]

Prints ``name,us_per_call,derived`` CSV rows.  Mapping to the paper:

    bench_compaction   Figure 5 + §5.2/§5.3 compaction claims (>99%, >99.9%)
    bench_mapping      §7 evaluation (per-event latency; Alg.1 vs Alg.6 A/B)
    bench_update       §3.5/§5.4 update cost (~100k elements per version add)
    bench_moe          model-side DMM (MoE dispatch impls A/B)
    bench_train_step   per-family step cost regression tracker
    bench_replication  §6 control plane: replication lag + failover cost

``--smoke`` is forwarded to modules whose ``run()`` accepts it (tiny shapes,
CI-sized).  ``--artifact DIR`` writes one ``BENCH_<unix-ts>.json`` trajectory
artifact into DIR after the run: the CSV rows plus every module's
``PERF_METRICS`` (name -> events/s, diffed against the last checked-in
artifact by ``scripts/perf_diff.py``) and ``ENGINE_METRICS`` (per-engine
per-chunk facts for ``repro.launch.roofline --etl``).  Module-level
``GATE_FAILURES`` lists are collected and fail the harness exactly like an
exception would.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

MODULES = [
    "bench_compaction",
    "bench_mapping",
    "bench_update",
    "bench_moe",
    "bench_train_step",
    "bench_replication",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, metavar="NAMES",
                    help="comma-separated module-name substrings, e.g. "
                         "'mapping,compaction'")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, CI-sized (modules that support it)")
    ap.add_argument("--artifact", default=None, metavar="DIR",
                    help="write a BENCH_<ts>.json trajectory artifact to DIR")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    print("name,us_per_call,derived")
    failed = 0
    all_rows = []
    perf_metrics = {}
    engine_metrics = []
    gate_failures = []
    only = [s for s in (args.only or "").split(",") if s]
    for modname in MODULES:
        if only and not any(s in modname for s in only):
            continue
        try:
            mod = __import__(modname)
            kwargs = {}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters:
                kwargs["smoke"] = True
            for name, us, derived in mod.run(**kwargs):
                all_rows.append({"name": name, "us": us, "derived": derived})
                print(f"{name},{us:.1f},{derived}", flush=True)
            perf_metrics.update(getattr(mod, "PERF_METRICS", {}))
            engine_metrics.extend(getattr(mod, "ENGINE_METRICS", []))
            gates = getattr(mod, "GATE_FAILURES", [])
            if gates:
                failed += 1
                gate_failures.extend(f"{modname}: {g}" for g in gates)
        except Exception:
            failed += 1
            print(f"{modname},ERROR,", flush=True)
            traceback.print_exc(file=sys.stderr)
    for msg in gate_failures:
        print(f"GATE FAILURE: {msg}", file=sys.stderr)
    if args.artifact:
        import jax

        os.makedirs(args.artifact, exist_ok=True)
        ts = int(time.time())
        path = os.path.join(args.artifact, f"BENCH_{ts}.json")
        with open(path, "w") as f:
            json.dump(
                {
                    "ts": ts,
                    "backend": jax.default_backend(),
                    "smoke": args.smoke,
                    "only": args.only,
                    "gate_failures": gate_failures,
                    "perf_metrics": perf_metrics,
                    "engines": engine_metrics,
                    "rows": all_rows,
                },
                f,
                indent=1,
            )
        print(f"artifact: {path}", file=sys.stderr)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
