"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases -- driven here at a tiny scenario, with the Pallas kernels under the
interpreter -- agree with the ``consume_scalar`` oracle row for row.

Also the one-process-per-chip guards around the mapping path: nothing that
runs next to the chip starts a child process that would contend for it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import os
import subprocess
import sys

import pytest

from _subproc import run_sub

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from repro.core.synthetic import ScenarioConfig  # noqa: E402

TINY = ScenarioConfig(
    n_schemas=8, versions_per_schema=3, attrs_per_version=6,
    n_entities=2, cdm_attrs=12, seed=7,
)


def _tiny(spec: cs.PhaseSpec) -> cs.PhaseSpec:
    return dataclasses.replace(
        spec, n_chunks=6, churn_at=3, chunk_size=min(spec.chunk_size, 64)
    )


def test_refuses_cpu_and_names_the_platform():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("spec", cs.ONE_CHIP, ids=lambda s: s.name.replace(" ", "_"))
def test_phase_matches_scalar_oracle(spec):
    res = cs.run_phase(TINY, _tiny(spec), impl="fused")
    cs.check_phase(res, cs.oracle_rows(TINY, res.items))
    assert sum(len(rows) for rows in res.rows) > 0
    assert res.info["device_densify"] == spec.device_densify


def test_check_phase_catches_a_wrong_value():
    res = cs.run_phase(TINY, _tiny(cs.ONE_CHIP[1]), impl="fused")
    want = cs.oracle_rows(TINY, res.items)
    i = next(i for i, rows in enumerate(res.rows) if rows)
    key, r, w, payload = res.rows[i][0]
    (uid, bits), *rest = payload
    res.rows[i][0] = (key, r, w, ((uid, bits ^ 1), *rest))
    with pytest.raises(AssertionError, match="differ from consume_scalar"):
        cs.check_phase(res, want)


def test_kernels_match_ref_twins_interpreted():
    assert cs.check_kernels(interpret=True) == [
        "segmented_gather", "densify_map", "masked_gather", "onehot_map",
    ]


def test_sharded_phases_match_fused_and_oracle():
    """The ``--chips 4`` comparison on four virtual CPU devices."""
    out = run_sub(
        f"""
        import dataclasses, sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke as cs
        from repro.core.synthetic import ScenarioConfig
        from repro.launch.mesh import make_etl_mesh
        tiny = ScenarioConfig(n_schemas=8, versions_per_schema=3,
                              attrs_per_version=6, n_entities=2, cdm_attrs=12, seed=7)
        mesh = make_etl_mesh(4)
        for spec in cs.FOUR_CHIPS:
            spec = dataclasses.replace(spec, n_chunks=6, churn_at=3, chunk_size=64)
            res = cs.run_phase(tiny, spec, impl="fused", mesh=mesh)
            cs.check_phase(res, cs.oracle_rows(tiny, res.items))
            twin = cs.run_phase(tiny, dataclasses.replace(spec, engine="fused"), impl="fused")
            assert twin.rows == res.rows
            assert res.table_devices == 4 and res.info["n_shards"] == 4
            print("ok", spec.name)
        """,
        devices=4,
    )
    assert out.count("ok ") == len(cs.FOUR_CHIPS)


def test_bench_sharded_ab_stays_in_process_off_cpu(monkeypatch):
    """Off the CPU backend the sharded A/B never starts a child: with too
    few devices it reports a row that did not run."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import bench_mapping

    monkeypatch.setattr(bench_mapping.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(bench_mapping.jax, "device_count", lambda: 1)

    def no_child(*a, **k):
        raise AssertionError("started a child process")

    monkeypatch.setattr(bench_mapping.subprocess, "run", no_child)
    [(name, us, derived)] = bench_mapping._sharded_ab(2, smoke=True)
    assert math.isnan(us) and derived == "not run: 1 devices"


def test_replication_smoke_children_run_on_cpu():
    spec = importlib.util.spec_from_file_location(
        "replication_smoke", os.path.join(REPO, "scripts", "replication_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.ENV["JAX_PLATFORMS"] == "cpu"
