"""Schema churn in a cell's window: in-band ``SchemaEvolved`` events on a
schedule, and the producers that follow them.

A traffic file with a ``churn`` block has its evolutions on a schedule:
with open-loop arrivals, ``{"every_s": 2.0, "first_s": 1.0}`` has one due
at ``first_s``, ``first_s + every_s``, ... into the window, before the
first event due at or after it; in a backlog, ``{"every_events": 200000,
"first_event": 100000}`` has one before event ``first_event``,
``first_event + every_events``, ... of the topic.  Each evolution draws a
schema uniformly from the run's seed and cuts its next version with the
deployment's own version-chain draw (``build_history``'s ``p_drop`` and
``p_add``, at most three fresh names), drawn again where the version keeps
no name that maps (:func:`draw`).  Kept names keep their mapping (the paper's equivalence copy); fresh names
map nowhere, since no one edits the matrix (:meth:`History.evolve`).

From an evolution's due time its schema's producers emit the new version:
the window's events are generated per registry state and joined, each
stamped with the state it was produced at.  The uids a producer stamps come
from a producer-side copy of the registry (:func:`deployment.registry_for`,
evolved here in set-up): the producers' schema-registry client.  The
program learns of an evolution only from the in-band event, which the
source yields at its due time, before the first event due at or after it
(:class:`bench.lib.source.OpenLoopSource`'s ``control``).  The reference
follows the benchmark's own ``History`` and ``Tables`` through every state.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

from . import deployment as dep
from . import traffic as trf

@dataclasses.dataclass(frozen=True)
class Evolution:
    """One scheduled evolution: due ``at`` seconds into the window, before
    event ``first`` (the first due at or after it)."""

    at: float
    first: int
    schema: int
    keep: Tuple[str, ...]
    add: Tuple[str, ...]


def times(churn: dict, seconds: float) -> List[float]:
    """Due offsets (s) of the window's evolutions under open-loop arrivals."""
    every, first = float(churn["every_s"]), float(churn["first_s"])
    out, k = [], 0
    while first + k * every < seconds:
        out.append(first + k * every)
        k += 1
    return out


def schedule(churn: dict, due: np.ndarray, seconds: float) -> List[Tuple[float, int]]:
    """``(at, first)`` of each evolution: its due offset (s) and the index
    of the first event after it (see the module doc)."""
    if "every_events" in churn:
        every, first = int(churn["every_events"]), int(churn["first_event"])
        return [(float(due[f]), f) for f in range(first, due.size, every)]
    at = times(churn, seconds)
    return list(zip(at, (int(f) for f in np.searchsorted(due, at, side="left"))))


def cut(rng: np.random.Generator, history: dep.History, o: int, reg: dict):
    """The next version of schema ``o``, drawn as ``build_history`` draws a
    version chain: (kept names, fresh names)."""
    prev = history.versions[o][-1]
    keep = [n for n in prev if rng.random() > reg["p_drop"]]
    add: List[str] = []
    fresh = len(history.slot[o])  # every name the schema ever had
    while rng.random() < reg["p_add"] and len(add) < 3:
        add.append(f"s{o}.a{fresh}")
        fresh += 1
    if not keep and not add:  # never cut an empty version
        keep = [prev[0]]
    return keep, add


def draw(rng: np.random.Generator, history: dep.History, reg: dict, tries: int = 1000):
    """A schema drawn uniformly and its next version cut (:func:`cut`),
    drawn again until the version keeps a name that maps somewhere: a
    rollout the mapping has to follow.  A version that keeps no mapped name
    adds no column to the mapping plan; in a window that takes such a draw
    the program re-plans for nothing, so seeds would differ in the work
    their windows hold.  Returns ``(schema, keep, add)``."""
    for _ in range(tries):
        o = int(rng.integers(len(history.versions)))
        keep, add = cut(rng, history, o, reg)
        if any(history.slot[o][n] >= 0 for n in keep):
            return o, keep, add
    raise RuntimeError("no schema's next version keeps a mapped name")


def event(evo: Evolution):
    """The in-band control event the source yields for ``evo``."""
    from repro.etl import SchemaEvolved

    return SchemaEvolved(tree="domain", schema_id=evo.schema, keep=evo.keep, add=evo.add)


def generate(rng, stream: dict, weights: np.ndarray, deployment: dep.Deployment,
             churn: dict, due: np.ndarray, seconds: float):
    """The window's events under churn, and its evolutions.

    Appends each new version to ``deployment``'s history and tables (the
    benchmark's own, which the reference reads); the program's registry is
    left alone."""
    history, tables = deployment.history, deployment.tables
    reg = deployment.spec["registry"]
    producer = dep.registry_for(history)
    if producer.state != deployment.state:
        raise RuntimeError("the producers' registry copy is not at the program's state")
    sched = schedule(churn, due, seconds)
    edges = [0, *(f for _, f in sched), int(due.size)]
    parts, evolutions = [], []
    for k in range(len(sched) + 1):
        lo, hi = edges[k], edges[k + 1]
        parts.append(trf.generate(rng, stream, weights, tables.version_cols(), tables.flat(),
                                  hi - lo, lo, producer.state))
        if k == len(sched):
            break
        o, keep, add = draw(rng, history, reg)
        producer.evolve(producer.domain, o, keep=keep, add=add)
        tables.add(history, producer, o, history.evolve(o, keep, add))
        evolutions.append(Evolution(sched[k][0], edges[k + 1], o, tuple(keep), tuple(add)))
    return trf.concat(parts), evolutions
