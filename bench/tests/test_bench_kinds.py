"""The deployment-kind seam (``bench/kinds/``): a kind that lives only in
the tests (``fixed_schemas``) runs through ``run_cell.run`` on the CPU and
is correct, its control and planted faults are not; a deployment file that
names no known kind, and churn traffic on a kind without churn, fail at
set-up; the harness itself reaches no EOS module."""

import ast

import pytest

from bench.tests import fixed_schemas, test_bench_run
from bench.tests.conftest import BENCH, run_small

BENCH_FIXED = {
    "workloads": [
        {"name": "fixed.replay", "config": "fixed", "traffic": "replay", "chips": 1},
        {"name": "fixed.live", "config": "fixed", "traffic": "live", "chips": 1},
    ],
    "end_to_end": [
        {"name": "events_per_s", "unit": "events/s", "workloads": ["fixed.replay"]},
        {"name": "latency_p50_ms", "unit": "ms", "workloads": ["fixed.live"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}

TRAFFIC = {
    "fixed.replay": {"config": "fixed", "arrival": {"kind": "backlog", "backlog_events": 4000},
                     "max_poll_records": 1000},
    "fixed.live": {"config": "fixed", "arrival": {"kind": "poisson", "rate_events_per_s": 4000.0},
                   "max_poll_records": 200, "drain_timeout_s": 60},
}


def _run(cell, traffic=None, **kw):
    return run_small(cell, fixed_schemas.spec(), traffic or dict(TRAFFIC[cell]),
                     bench=BENCH_FIXED, kind=fixed_schemas, **kw)


def _numbers(res):
    return {k: v["value"] for k, v in res["check"].items()}


@pytest.mark.parametrize("cell", sorted(TRAFFIC))
def test_kind_of_the_tests_is_correct(cell, monkeypatch):
    compared = []
    compare = fixed_schemas.compare

    def spy(want, got):
        compared.append((want.n, got.n))
        return compare(want, got)

    monkeypatch.setattr(fixed_schemas, "compare", spy)
    res = _run(cell)
    assert res["correct"], _numbers(res)
    assert set(_numbers(res).values()) == {0}
    assert res["attempted"] > 1000 and res["failed"] == 0
    (want, got), = compared
    assert want == got > 1000
    assert set(res["metrics"]) == {"setup_s", "events_per_s" if cell == "fixed.replay"
                                   else "latency_p50_ms"}


def test_kind_of_the_tests_control_fails():
    res = _run("fixed.replay", control="bf16")
    assert not res["correct"]
    assert _numbers(res)["rows_wrong"] > 1000


@pytest.mark.parametrize("fault, number", [
    (test_bench_run._drop_half, "rows_missing"),
    (test_bench_run._alter_one_value, "rows_wrong"),
])
@pytest.mark.parametrize("cell", sorted(TRAFFIC))
def test_planted_fault_in_the_kind_of_the_tests(fault, number, cell, monkeypatch):
    from bench.lib.source import OpenLoopSource

    start = OpenLoopSource.start

    def open_window(self, t0, stop_at):
        fault(monkeypatch)
        start(self, t0, stop_at)

    monkeypatch.setattr(OpenLoopSource, "start", open_window)
    res = _run(cell)
    assert not res["correct"]
    assert _numbers(res)[number] > 0, _numbers(res)


@pytest.mark.parametrize("kind", ["nexmark_q9", None, "../lib/traffic"])
def test_unknown_kind_fails_at_set_up(kind, small_spec, small_traffic, monkeypatch):
    from bench import run_cell

    monkeypatch.setattr(run_cell, "_app", lambda d, e: pytest.fail("set-up went on"))
    if kind is None:
        del small_spec["kind"]
    else:
        small_spec["kind"] = kind
    with pytest.raises(ValueError) as err:
        run_small("eos_paper.replay", small_spec, small_traffic("eos_paper.replay"))
    assert "bench/deployments/eos_paper.json" in str(err.value)
    assert repr(kind) in str(err.value)


def test_churn_traffic_on_a_kind_without_churn_fails_at_set_up(monkeypatch):
    from bench import run_cell

    monkeypatch.setattr(run_cell, "_app", lambda d, e: pytest.fail("set-up went on"))
    assert not hasattr(fixed_schemas, "churn")
    traffic = {**TRAFFIC["fixed.replay"], "churn": {"every_events": 1000, "first_event": 500}}
    with pytest.raises(ValueError, match="'fixed_schemas' has no churn"):
        _run("fixed.replay", traffic)


def test_the_harness_reaches_the_deployment_only_through_its_kind():
    """``run_cell.py`` imports no EOS module and calls no EOS generator;
    neither it nor ``bench/lib`` knows the kind of the tests."""
    src = (BENCH / "run_cell.py").read_text()
    tree = ast.parse(src)
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            imported |= {f"{node.module}.{a.name}" for a in node.names}
        elif isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
    for mod in ("deployment", "reference", "churn"):
        assert not any(f"bench.lib.{mod}" in m for m in imported), mod
    calls = {f"{n.value.id}.{n.attr}" for n in ast.walk(tree)
             if isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name)}
    assert not calls & {"trf.generate", "trf.schema_weights"}
    for path in [BENCH / "run_cell.py", *(BENCH / "lib").glob("*.py")]:
        assert "fixed_schemas" not in path.read_text(), path


INTERFACE = ("build", "weights", "generate", "chunk", "event", "expected", "program_rows",
             "compare", "kernel_bytes")


def test_every_deployment_names_a_kind_that_serves_its_cells():
    """Each configuration's file names a kind with the whole interface
    (the kind of the tests too), and ``churn`` where a cell's traffic has
    a churn block."""
    import json

    from bench import run_cell
    from bench.lib import traffic as trf

    bench = run_cell.load_benchmark()
    for c in bench["configs"]:
        kind = run_cell.load_kind(json.loads((BENCH.parent / c["file"]).read_text()), c["file"])
        assert all(callable(getattr(kind, f, None)) for f in INTERFACE), c["name"]
        for w in bench["workloads"]:
            if w["config"] == c["name"] and "churn" in trf.load_traffic(BENCH, w["name"]):
                assert callable(getattr(kind, "churn", None)), w["name"]
    assert all(callable(getattr(fixed_schemas, f, None)) for f in INTERFACE)
