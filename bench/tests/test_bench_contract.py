"""BENCHMARK.json against the layout the harness finds by name, and the
runner's refusal to run without a TPU."""

import json
import os
import re
import subprocess
import sys

from bench.tests.conftest import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_every_name_finds_its_file():
    b = _bench()
    assert b["command"] == ["python3", "bench/run_cell.py"] and b["paths"] == ["bench"]
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert (REPO / c["file"]).is_file() and c["file"].startswith("bench/")
        spec = json.loads((REPO / c["file"]).read_text())
        assert spec["name"] == c["name"] and spec["reduced"] == c["reduced"]
    cells = {w["name"] for w in b["workloads"]}
    for w in b["workloads"]:
        assert w["config"] in configs and w["chips"] == 1
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        t = json.loads((BENCH / "traffic" / f"{w['name']}.json").read_text())
        assert t["config"] == w["config"]
        assert len(w["why"]) <= 200
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", sorted(cells))
        assert set(m["workloads"]) <= set(moved) and set(m["workloads"]) <= cells
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for cell in cells:
        reported = [m for m in b["end_to_end"] if cell in m.get("workloads", [cell])]
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in b["per_layer"])


def test_runner_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run_cell.py"), "--workload", "eos_paper.replay",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr
