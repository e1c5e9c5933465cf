"""The EOS deployment kind (arXiv:2203.10289 §3.5): schema histories drawn
from the deployment file's seed, float32 values, 1:1 permutations into
append-only entity tables.  An adapter over ``bench/lib``: the deployment
builder, the generator, the source's float32 chunks, the plain reference,
the churn schedule and the kernel byte count."""

from __future__ import annotations

import numpy as np

from bench.lib import churn as chn
from bench.lib import deployment as dep
from bench.lib import reference as ref
from bench.lib import traffic as trf
from bench.lib.kernel_bytes import chunk_bytes
from bench.lib.source import cdc_event, columnar_chunk, routed

build = dep.build
chunk = columnar_chunk
event = cdc_event
compare = ref.compare


def weights(spec: dict, traffic: dict) -> np.ndarray:
    reg = spec["registry"]
    return trf.schema_weights(reg["n_schemas"],
                              traffic.get("skew", {}).get("schema_zipf_s", 0.0), reg["seed"])


def generate(d: dep.Deployment, w, rng, stream: dict, n: int, key0: int):
    t = d.tables
    return routed(trf.generate(rng, stream, w, t.version_cols(), t.flat(), n, key0, d.state),
                  t.cols)


def churn(d: dep.Deployment, w, rng, stream: dict, churn: dict, due, seconds: float):
    batch, evolutions = chn.generate(rng, stream, w, d, churn, due, seconds)
    return routed(batch, d.tables.cols), [(e.first, e.at, chn.event(e)) for e in evolutions]


def expected(d: dep.Deployment, batch, passes, control: str) -> ref.Rows:
    """The control computes the values in bfloat16 first."""
    if control == "none":
        dtype = np.float32
    elif control == "bf16":
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    else:
        raise ValueError(f"unknown control {control!r}")
    width = max(d.tables.n_out)
    return ref.concat_rows(
        [ref.expected_rows(batch, d.tables, 0, take, width, key_shift=shift, value_dtype=dtype)
         for take, shift in passes] or [ref.empty_rows(width)])


def program_rows(d: dep.Deployment, arrays) -> ref.Rows:
    return ref.table_rows(arrays, max(d.tables.n_out))


def kernel_bytes(d: dep.Deployment, batch, polls) -> int:
    """Bytes the mapping of the polled chunks needs (see kernel_bytes): an
    event counts where it is its key's first delivery and its column maps
    somewhere."""
    tables = d.tables
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
