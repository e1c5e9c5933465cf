#!/usr/bin/env python3
"""Run one cell of METL's chip benchmark and print its result line.

    python bench/run_cell.py --workload eos_paper.replay --seed 12345 \\
        --seconds 20 --trace 0

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a deployment
(``bench/deployments/<config>.json``) under a traffic mix
(``bench/traffic/<cell>.json``).  The run builds the deployment through the
program's public API, generates every event of the window from ``--seed``,
warms up every chunk shape the traffic will use (set-up), then drives
``Pipeline`` over ``METLApp`` into a ``TableSink`` from an open-loop source
for ``--seconds``.  After the window it compares every row the window
produced with the plain reference (``bench/lib/reference.py``).

The ``TableSink`` keeps every row of the window for that comparison, which
a deployment's sink, handing rows to its warehouse, would not.  So that
Python's collector does not walk that growing record (a full collection
would take 0.1 s and more as it grows), each chunk's write is followed by
``gc.freeze()``: what is alive then is left out of later collections, and
the collector works on what the program makes after it.  Every collection
in the window is recorded (``gc.callbacks``) and logged, and in traced
runs it is a host span named ``gc``.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read by ``bench/metrics/<name>.py``
from host spans, counters and the device trace.  The last line of stdout
is one JSON object; the numbers compared for ``correct`` are also the last
lines of stderr.  Without a TPU (or with fewer chips than the cell asks
for) the run exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench.lib import deployment as dep  # noqa: E402
from bench.lib import traffic as trf  # noqa: E402

WARM_KEYS = 1 << 40  # warm-up events' keys: apart from every window key


def load_benchmark(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_spec(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, name: str, kind: str) -> List[dict]:
    return [m for m in bench[kind] if name in m.get("workloads", [name])]


def warm_sizes(traffic: dict) -> List[int]:
    """Chunk sizes the warm-up drives: full polls for a backlog; for open
    arrivals every poll size from 1 to ``max_poll`` in steps of at most 3%,
    so that each bucketed shape the window can meet compiles in set-up."""
    cap = traffic["max_poll_records"]
    if traffic["arrival"]["kind"] == "backlog":
        return [cap] * 24
    sizes, n = [], 1
    while n < cap:
        sizes.append(n)
        n = max(n + 1, int(n * 1.03))
    return sizes + [cap]


# Warm-up chunks of each size come in four mixes, so that the counts that
# shape a chunk's program (its items, and the items of its fullest event)
# span what the window can meet: the stream's own; a quarter and half of
# the values null; no event over 8 items.
WARM_MIXES = ({}, {"p_null": 0.25}, {"p_null": 0.5}, {"max_items": 8})


class Counters:
    """Backend compiles, from jax.monitoring.

    JAX records its backend-compile event around the persistent cache's
    lookup, so a program loaded from that cache fires it as a compile does:
    ``compiles`` counts both (``compiles_in_window.live`` reads it), and
    ``loads`` the cache-hit events, which only a load records.  ``secs``
    sums the backend-compile events' durations and ``ends`` holds the clock
    at the end of each.  The churn cell's window runs with the cache off,
    so there every event is a compile."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax

        self.compiles = self.loads = 0
        self.secs = 0.0
        self.ends: List[float] = []
        self.names: List[str] = []

        def on_hit(event: str, **kw: Any) -> None:
            if event == self.HIT:
                self.loads += 1

        def on(event: str, secs: float, **kw: Any) -> None:
            if event == self.EVENT:
                self.compiles += 1
                self.secs += secs
                self.ends.append(time.perf_counter())
                self.names.append(str(kw.get("fun_name", "?")))

        jax.monitoring.register_event_listener(on_hit)
        jax.monitoring.register_event_duration_secs_listener(on)


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    a fixed directory in the checkout (the path is part of the key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_off() -> None:
    """Turn JAX's persistent cache off for the rest of the process: nothing
    is loaded from it or written to it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _app(deployment: dep.Deployment, engine: dict):
    from repro.etl import METLApp

    return METLApp(
        deployment.coordinator,
        engine=engine["engine"],
        device_densify=engine["device_densify"],
        dedup_window=engine["dedup_window"],
    )


def _warm(app, engine: dict, chunks, counters: Counters) -> List[int]:
    """Drive each warm-up chunk through the cell's pipeline path; returns
    the indices of the chunks that compiled something."""
    from repro.etl import CollectSink, ListSource, Pipeline

    pipe = Pipeline(ListSource(chunks), app, [CollectSink()],
                    async_consume=engine["async_consume"])
    hits = []
    for k in range(len(chunks)):
        before = counters.compiles
        pipe.run(max_chunks=1)
        if counters.compiles != before:
            hits.append(k)
    pipe.close()
    app.reset_dedup()
    return hits


def _warm_chunks(rng, stream, weights, tables, sizes, state, key0):
    from bench.lib.source import OpenLoopSource

    chunks = []
    for m, mix in enumerate(WARM_MIXES if len(set(sizes)) > 1 else ({},)):
        batch = trf.generate(rng, {**stream, **mix}, weights, tables.version_cols(),
                             tables.flat(), int(sum(sizes)), key0 + m * (1 << 36), state)
        src = OpenLoopSource(batch, tables.cols, np.zeros(batch.n), max_poll=max(sizes))
        edges = np.cumsum([0] + list(sizes))
        chunks += [src.chunk(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])]
    return chunks


def run(
    cell: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    bench: Optional[dict] = None,
    deployment_spec: Optional[dict] = None,
    traffic: Optional[dict] = None,
    control: str = "none",
    t_process: float = T_PROCESS,
    trace_dir: Optional[str] = None,
    cache: bool = True,
    log=lambda *a: print(*a, file=sys.stderr, flush=True),
) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object (see module doc).
    ``deployment_spec``/``traffic`` override the cell's files (tests run a
    small deployment on the CPU this way, with ``cache=False`` to leave
    JAX's persistent cache settings alone)."""
    import jax
    from repro.etl import Pipeline, TableSink

    from bench.lib import reference as ref
    from bench.lib.source import OpenLoopSource, WriteClock, latencies
    from bench.lib.spans import GcPauses

    bench = bench or load_benchmark()
    spec = cell_spec(bench, cell)
    traffic = traffic or trf.load_traffic(BENCH, cell)
    dspec = deployment_spec or dep.load_spec(BENCH, spec["config"])
    engine, stream = dspec["engine"], dspec["stream"]
    cache_dir = use_compile_cache() if cache else "off"
    counters = Counters()
    rng = np.random.default_rng(int(seed) % (1 << 64))
    clock = time.perf_counter

    # -- the deployment, and the program's app over it ------------------------
    marks = {"start": clock()}
    real = dep.build(dspec)
    tables = real.tables
    marks["deployment"] = clock()
    weights = trf.schema_weights(len(real.history.versions),
                                 traffic.get("skew", {}).get("schema_zipf_s", 0.0),
                                 dspec["registry"]["seed"])
    app = _app(real, engine)
    sizes = warm_sizes(traffic)
    warm = _warm_chunks(rng, stream, weights, tables, sizes, real.state, WARM_KEYS)
    hits = _warm(app, engine, warm, counters)
    marks["warm-up"] = clock()
    log(f"warm-up: {len(warm)} chunks, {counters.compiles} compiles "
        f"({counters.loads} of them persistent-cache loads; {len(hits)} chunks compiled), "
        f"cache {cache_dir}")

    # -- the window's traffic --------------------------------------------------
    arrival = traffic["arrival"]
    backlog = arrival["kind"] == "backlog"
    churn = traffic.get("churn")
    if backlog:
        n = int(arrival["backlog_events"])
        if n % traffic["max_poll_records"]:
            raise ValueError("backlog_events must be a multiple of max_poll_records, "
                             "so that every poll of every pass is full")
        due = np.zeros(n)
    else:
        due = trf.arrivals(rng, arrival, seconds)
        n = due.size
    evolutions: List[Any] = []
    if churn:
        from bench.lib import churn as chn

        batch, evolutions = chn.generate(rng, stream, weights, real, churn, due, seconds)
        inband = [(e.first, e.at, chn.event(e)) for e in evolutions]
    else:
        batch = trf.generate(rng, stream, weights, tables.version_cols(), tables.flat(), n, 0,
                             real.state)
        inband = []
    marks["traffic"] = clock()
    if churn and cache:
        # a deployment meeting a new registry state has never compiled its
        # programs: no run may load them from an earlier one
        cache_off()
    table = TableSink()
    writes = WriteClock(clock, freeze=True)
    src = OpenLoopSource(batch, tables.cols, due, max_poll=traffic["max_poll_records"],
                         cycle=backlog, key_span=batch.n, clock=clock, annotate=trace,
                         control=inband)
    applies: List[Tuple[float, float]] = []

    def apply_timed(event) -> None:  # the pipeline's default, timed
        a = clock()
        app.coordinator.apply(event, defer_frozen=True)
        applies.append((a, clock()))

    pipe = Pipeline(src, app, [table, writes], async_consume=engine["async_consume"],
                    apply_control=apply_timed if churn else None)
    spans = None
    if trace:
        from bench.lib.spans import Spans

        spans = Spans(clock)
        spans.wrap(app, "triage", "triage")
        spans.wrap(app.engine, "densify", "densify")
        spans.wrap(app.engine, "dispatch", "dispatch")
        spans.wrap(app.engine, "emit", "emit")
        spans.wrap(table, "write", "sink")
        trace_dir = trace_dir or str(REPO / ".bench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gc.collect()
    gc.freeze()  # set-up's objects: out of the window's collections
    names = list(marks)
    log("set-up (s): imports and chip " + f"{marks['start'] - t_process:.2f}, " + ", ".join(
        f"{b} {marks[b] - marks[a]:.2f}" for a, b in zip(names[:-1], names[1:])))

    # -- the window ------------------------------------------------------------
    compiles0, loads0, secs0 = counters.compiles, counters.loads, counters.secs
    jax.config.update("jax_log_compiles", True)  # names what compiles in the window
    pauses = GcPauses(clock, annotate=trace)
    t0 = clock()
    mark = None
    if trace:
        with jax.profiler.TraceAnnotation("bench:open"):
            mark = clock()
    tw = t0 + seconds
    src.start(t0, tw if backlog else tw + float(traffic["drain_timeout_s"]))
    pipe.run()
    t_done = clock()
    pipe.close()
    pauses.close()
    jax.config.update("jax_log_compiles", False)
    compiles_window = counters.compiles - compiles0
    loads_window = counters.loads - loads0
    secs_window = counters.secs - secs0
    if compiles_window:
        log(f"compiled in the window: {counters.names[compiles0:]}")
    if trace:
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    setup_s = t0 - t_process
    gc.unfreeze()

    # -- what the window did ---------------------------------------------------
    polls = src.polls[: len(writes.times)]
    sizes_w = np.asarray([hi - lo for lo, hi, _ in polls], np.int64)
    wt = np.asarray(writes.times)
    in_win = wt <= tw
    n_written = int(sizes_w.sum())
    values: Dict[str, float] = {"setup_s": setup_s}
    if backlog:
        attempted = int(sum(hi - lo for lo, hi, _ in src.polls))
        unwritten = attempted - n_written
        values["events_per_s"] = int(sizes_w[in_win].sum()) / seconds
    else:
        attempted = int(batch.n)
        lat = latencies(due, polls, writes.times, t0, attempted)
        unwritten = int(np.isinf(lat).sum())
        finite = lat[np.isfinite(lat)]
        if finite.size:
            p50, p95, p99 = np.percentile(finite, [50, 95, 99]) * 1e3
            values.update(latency_p50_ms=float(p50), latency_p99_ms=float(p99))
            log(f"latency p50 / p95 / p99 over the window: {p50:.3f} / {p95:.3f} / {p99:.3f} ms")
        for pct in (50, 99):
            fifths = [np.percentile(q[np.isfinite(q)], pct) * 1e3 if np.isfinite(q).any()
                      else -1 for q in np.array_split(lat, 5)]
            log(f"latency p{pct} per fifth of the window (ms): "
                + " ".join(f"{x:.2f}" for x in fifths))
        log(f"backlog at the close: {int(np.searchsorted(due, seconds)) - int(sizes_w[in_win].sum())}"
            f" events due and not yet written")
    if evolutions:
        _log_churn(evolutions, src.controls, applies, wt, counters.ends, t0, tw, log)
    log(f"window: {seconds} s, {len(polls)} chunks, {n_written} events written "
        f"({int(sizes_w[in_win].sum())} by the close), run ended {t_done - tw:+.3f} s "
        f"after the close, {compiles_window} compiles in the window ({loads_window} "
        f"persistent-cache loads), {secs_window * 1e3:.1f} ms in them")
    log("collector in the window: " + pauses.summary(t0, tw))
    if in_win.sum() > 1:
        log(f"longest time between two writes in the window: "
            f"{np.diff(wt[in_win]).max() * 1e3:.2f} ms")

    # -- correct: every row against the reference -----------------------------
    t_ref = clock()
    width = max(tables.n_out)
    passes = [(min(n_written - q * batch.n, batch.n), q * batch.n)
              for q in range(-(-n_written // batch.n))]

    def reference(value_dtype=np.float32):
        return ref.concat_rows(
            [ref.expected_rows(batch, tables, 0, take, width, key_shift=shift,
                               value_dtype=value_dtype) for take, shift in passes]
            or [ref.empty_rows(width)])

    want = reference()
    if control == "bf16":
        import ml_dtypes

        got = reference(ml_dtypes.bfloat16)
    else:
        got = ref.table_rows(table.to_arrays(), width)
    check = ref.compare(want, got)
    check["events_unwritten"] = unwritten
    correct = all(v == 0 for v in check.values())
    log(f"reference: {want.n} rows expected, {got.n} rows written, "
        f"compared in {clock() - t_ref:.1f} s")

    # -- metrics ---------------------------------------------------------------
    metrics: Dict[str, Dict[str, Any]] = {}
    out_device: Dict[str, Any] = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": int(mem),
    }
    breakdown = None
    if not trace:
        for m in cell_metrics(bench, cell, "end_to_end"):
            # ``<quantity>.<traffic>`` is the quantity, named apart for a cell
            v = values.get(m["name"], values.get(m["name"].split(".")[0]))
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from bench.lib import trace as tr

        ops, host = tr.load(trace_dir)
        open_ns = host.start[host.name.index("open")] if "open" in host.name else None
        ctx = types.SimpleNamespace(
            cell=cell, t0=t0, tw=tw, seconds=seconds, spans=spans,
            events_in_window=int(sizes_w[in_win].sum()), chunks_in_window=int(in_win.sum()),
            poll_sizes=sizes_w[wt <= tw], compiles_in_window=compiles_window,
            loads_in_window=loads_window, compile_s_in_window=secs_window,
            evolutions_applied=len(src.controls),
            device_kind=dev.device_kind, reduction=None, kernel_bytes=None,
        )
        if open_ns is not None and ops:
            lo = int(open_ns)
            hi = lo + int((tw - mark) * 1e9)
            ctx.reduction = tr.reduce(ops[min(ops)], host, lo, hi, kernel="densify_map")
            red = ctx.reduction
            out_device["busy_s"] = red.busy_ns / 1e9
            out_device["window_s"] = red.window_ns / 1e9
            breakdown = {"device_ops": [[n, s] for n, s in red.top_ops],
                         "idle_gaps": [[n, s] for n, s in red.idle_gaps]}
        ctx.kernel_bytes = _kernel_bytes(batch, tables, [p for p, ok in zip(polls, in_win) if ok])
        for m in cell_metrics(bench, cell, "per_layer"):
            v = load_metric(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": unwritten,
        "metrics": metrics,
        "device": out_device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": v, "limit": 0} for k, v in check.items()}
    for k, v in check.items():
        log(f"check {k}: {v} (limit 0)")
    return result


def _log_churn(evolutions, yielded, applies, writes, ends, t0, tw, log) -> None:
    """Log where each evolution's time went, by the host's clock: the
    source's yield, the pipeline's drain up to the coordinator's apply, the
    apply, the next write, and the programs compiled before the next
    evolution's apply (or the run's end)."""
    ends, writes = np.asarray(ends), np.asarray(writes)
    for k, e in enumerate(evolutions):
        step = "never yielded"
        if k < len(yielded) and k < len(applies):
            (a, done), y = applies[k], yielded[k][1]
            nxt = applies[k + 1][0] if k + 1 < len(applies) else np.inf
            later = writes[writes > done]
            w = later[0] if later.size else np.nan
            c = int(((ends > done) & (ends < nxt)).sum())
            step = (f"yielded {y - t0:.3f} s into the window (due {e.at:.3f} s), drain "
                    f"{(a - y) * 1e3:.1f} ms, apply {(done - a) * 1e3:.1f} ms, "
                    f"next write {(w - done) * 1e3:.1f} ms after it, {c} compiles before "
                    f"the next evolution's apply")
        log(f"evolution {k} before event {e.first}: schema {e.schema} keeps {len(e.keep)}, "
            f"adds {len(e.add)}; {step}")
    log("compiles end at (s into the window): " + " ".join(
        f"{c - t0:.2f}" for c in ends if c > t0))
    log(f"evolutions applied by the close: {sum(1 for _, d in applies if d <= tw)} of "
        f"{len(evolutions)} ({len(applies)} by the run's end)")


def _kernel_bytes(batch, tables, polls) -> int:
    """Bytes the mapping of the polled chunks needs (see kernel_bytes): an
    event counts where it is its key's first delivery and its column maps
    somewhere."""
    from bench.lib.kernel_bytes import chunk_bytes

    first = np.r_[True, batch.key[1:] != batch.key[:-1]]
    mapped = np.asarray([bool((p >= 0).any()) for p in tables.cdm_pos])[batch.col]
    sel = first & mapped
    items = np.diff(batch.offsets)
    n_out = np.asarray(tables.n_out)
    total = 0
    for lo, hi, _ in polls:
        i, j = lo % batch.n, (hi - 1) % batch.n + 1
        s = sel[i:j]
        col = batch.col[i:j][s]
        total += chunk_bytes(int(items[i:j][s].sum()), int(s.sum()), int(s.sum()),
                             n_out[np.unique(col)], n_out[col])
    return total


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("none", "bf16"), default="none",
                    help="compare the reference computed with bfloat16 values in "
                         "the program's place (the control that must fail)")
    args = ap.parse_args(argv)
    bench = load_benchmark()
    spec = cell_spec(bench, args.workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < spec["chips"]:
        print(f"run_cell: needs {spec['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    result = run(args.workload, args.seed, args.seconds, bool(args.trace), bench=bench,
                 control=args.control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
