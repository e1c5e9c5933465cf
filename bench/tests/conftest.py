"""CPU-only fixtures for the benchmark's tests: a small deployment and
small traffic in the cells' own formats.  Nothing here loads a TPU
library."""

import copy
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import deployment as dep  # noqa: E402
from bench.lib import traffic as trf  # noqa: E402

BENCH = REPO / "bench"


@pytest.fixture
def small_spec():
    """``eos_paper``'s semantics over a registry small enough for a test."""
    spec = copy.deepcopy(dep.load_spec(BENCH, "eos_paper"))
    spec["registry"].update(n_schemas=8, versions_per_schema=3, attrs_per_version=6,
                            n_entities=4, cdm_attrs=10)
    return spec


@pytest.fixture
def small_traffic():
    """Traffic files of the cells, shrunk: ``small_traffic(cell)``."""

    def make(cell):
        t = copy.deepcopy(trf.load_traffic(BENCH, cell))
        if t["arrival"]["kind"] == "backlog":
            t["arrival"]["backlog_events"] = 4000
        else:
            t["arrival"]["rate_events_per_s"] = 4000.0
            t["max_poll_records"] = 200
        return t

    return make


def run_small(cell, spec, traffic, seconds=0.6, seed=2**31 + 7, **kw):
    from bench import run_cell

    return run_cell.run(cell, seed, seconds, kw.pop("trace", False),
                        deployment_spec=spec, traffic=traffic, cache=False,
                        t_process=0.0, log=lambda *a: None, **kw)

