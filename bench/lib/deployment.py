"""A deployment: its schema history, its ground-truth mapping, and the
program's registry built from them.

The history (which attributes each schema version has, by name) and the
ground-truth mapping (which CDM attribute each extraction attribute feeds)
are drawn here from the deployment file's seed, by the benchmark's own
code.  They follow the EOS scenario of arXiv:2203.10289 §3.5: version
chains in which attributes survive by name, are sometimes dropped and
sometimes added, and each extraction schema maps 1:1 into one business
entity.  The draw is the same as the program's scenario builder makes, so
the sizes match the chip bring-up's (643,072 B block table for
``eos_paper``), but it is a copy: a change to the program cannot move it.

The program is reached only through its public registry and matrix API
(``Registry``, ``MappingMatrix``, ``transform_to_dpm``,
``StateCoordinator``).  The reference (:mod:`bench.lib.reference`) reads
only the name-level history kept here, never the program's DPM or plan.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

Column = Tuple[int, int]  # (schema o, version v)


@dataclasses.dataclass
class History:
    """Name-level schema history plus the ground-truth mapping.

    ``versions[o]`` lists each version's attribute names in order;
    ``slot[o][name]`` is the CDM attribute position the attribute feeds, or
    -1 where it maps nowhere.  Attributes that survive into a new version
    keep their name and therefore their mapping (the paper's equivalence
    copy); a name added by a later evolution maps nowhere until someone
    edits the matrix.
    """

    n_entities: int
    cdm_names: List[List[str]]
    versions: Dict[int, List[List[str]]]
    slot: Dict[int, Dict[str, int]]

    def entity(self, o: int) -> int:
        return o % self.n_entities

    def latest(self, o: int) -> int:
        return len(self.versions[o])

    def evolve(self, o: int, keep: List[str], add: List[str]) -> int:
        """Cut version v+1 of schema ``o``; returns the new version."""
        for name in add:
            self.slot[o][name] = -1
        self.versions[o].append(list(keep) + list(add))
        return self.latest(o)


def build_history(reg: dict) -> History:
    """Draw a deployment's schema history and mapping from its seed."""
    rng = np.random.default_rng(reg["seed"])
    n_e, w_cdm = reg["n_entities"], reg["cdm_attrs"]
    cdm_names = [[f"be{r}.c{k}" for k in range(w_cdm)] for r in range(n_e)]
    versions: Dict[int, List[List[str]]] = {}
    for o in range(reg["n_schemas"]):
        names = [f"s{o}.a{k}" for k in range(reg["attrs_per_version"])]
        chain = [names]
        fresh = reg["attrs_per_version"]
        for _ in range(reg["versions_per_schema"] - 1):
            prev = chain[-1]
            keep = [n for n in prev if rng.random() > reg["p_drop"]]
            add: List[str] = []
            while rng.random() < reg["p_add"] and len(add) < 3:
                add.append(f"s{o}.a{fresh}")
                fresh += 1
            if not keep and not add:  # never cut an empty version
                keep = [prev[0]]
            chain.append(keep + add)
        versions[o] = chain
    slot: Dict[int, Dict[str, int]] = {}
    for o in range(reg["n_schemas"]):
        free = list(range(w_cdm))
        rng.shuffle(free)
        slot[o] = {}
        for names in versions[o]:
            for name in names:
                if name not in slot[o]:
                    if free and rng.random() < reg["map_density"]:
                        slot[o][name] = free.pop()
                    else:
                        slot[o][name] = -1
    return History(n_entities=n_e, cdm_names=cdm_names, versions=versions, slot=slot)


def register(history: History):
    """The program's registry and DPM for a history (public API only)."""
    from repro.core.dmm import MappingMatrix, transform_to_dpm
    from repro.core.registry import Registry

    reg = Registry()
    for r, names in enumerate(history.cdm_names):
        reg.add_schema(reg.range, r, names)
    for o, chain in history.versions.items():
        reg.add_schema(reg.domain, o, chain[0])
        for names in chain[1:]:
            latest = reg.domain.get(o, reg.domain.latest_version(o))
            prev = {a.name for a in latest.attributes}
            reg.evolve(reg.domain, o, keep=[n for n in names if n in prev],
                       add=[n for n in names if n not in prev])
    matrix = MappingMatrix(reg)
    for o, chain in history.versions.items():
        r = history.entity(o)
        cdm_uids = reg.range.get(r, 1).uids
        for v, names in enumerate(chain, start=1):
            for a in reg.domain.get(o, v).attributes:
                pos = history.slot[o][a.name]
                if pos >= 0:
                    matrix.set(cdm_uids[pos], a.uid, 1)
    matrix.validate_one_to_one()
    return reg, transform_to_dpm(matrix)


@dataclasses.dataclass
class Tables:
    """Per-column arrays for the generator and the reference, append-only
    across schema evolutions (a new version is a new column).

    ``uid[c]`` are the program registry's attribute ids of column ``c`` (what
    a producer stamps on its records); ``cdm_pos[c]`` are the ground-truth
    CDM positions of the same attributes, from the history alone.
    """

    cols: List[Column]
    entity: List[int]
    uid: List[np.ndarray]
    cdm_pos: List[np.ndarray]
    n_out: List[int]

    def add(self, history: History, registry, o: int, v: int) -> int:
        names = history.versions[o][v - 1]
        sv = registry.domain.get(o, v)
        if [a.name for a in sv.attributes] != names:
            raise RuntimeError(f"registry column ({o}, {v}) differs from the history")
        r = history.entity(o)
        self.cols.append((o, v))
        self.entity.append(r)
        self.uid.append(np.asarray(sv.uids, np.int32))
        self.cdm_pos.append(np.asarray([history.slot[o][n] for n in names], np.int32))
        self.n_out.append(len(history.cdm_names[r]))
        return len(self.cols) - 1

    def flat(self):
        """Column-major flat arrays: (start, count, uid_flat, pos_flat)."""
        count = np.asarray([u.size for u in self.uid], np.int64)
        start = np.zeros(count.size, np.int64)
        np.cumsum(count[:-1], out=start[1:])
        return start, count, np.concatenate(self.uid), np.concatenate(self.cdm_pos)


def tables_for(history: History, registry) -> Tables:
    t = Tables(cols=[], entity=[], uid=[], cdm_pos=[], n_out=[])
    for o, chain in history.versions.items():
        for v in range(1, len(chain) + 1):
            t.add(history, registry, o, v)
    return t


@dataclasses.dataclass
class Deployment:
    """One configuration as run: its file, history, the program's
    coordinator over the registry built from it, and the column tables."""

    spec: dict
    history: History
    coordinator: object
    tables: Tables

    @property
    def state(self) -> int:
        return self.coordinator.registry.state


def load_spec(root: Path, name: str) -> dict:
    return json.loads((root / "deployments" / f"{name}.json").read_text())


def build(spec: dict) -> Deployment:
    from repro.core.state import StateCoordinator

    history = build_history(spec["registry"])
    registry, dpm = register(history)
    return Deployment(
        spec=spec,
        history=history,
        coordinator=StateCoordinator(registry, dpm),
        tables=tables_for(history, registry),
    )
