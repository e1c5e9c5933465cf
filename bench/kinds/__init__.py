"""Deployment kinds: what a run does that depends on the shape of its
deployment.

A deployment file (``bench/deployments/<config>.json``) names its kind
under ``"kind"``, and ``bench/run_cell.py`` loads ``bench/kinds/<kind>.py``.
The harness keeps the arrivals, polls, timing, warm-up policy, window and
metrics; the kind builds the deployment, its traffic and its reference.
A kind is a module of plain functions (``d`` is what ``build`` returns):

``build(spec)``
    The deployment: ``d.coordinator`` is the program's
    ``StateCoordinator``, which ``METLApp`` runs on, and ``d.state`` its
    registry state.
``weights(spec, traffic)``
    The stream's popularity, which ``generate`` draws from (``w``).
``generate(d, w, rng, stream, n, key0)``
    ``n`` events at ``d.state``, keys from ``key0``, drawn from ``rng``;
    ``stream`` is the deployment file's, with the harness's warm-up mixes
    (``p_null``, ``max_items``) laid over it.  The batch has ``n`` and
    ``state`` (each event's registry state); the rest is the kind's own.
``chunk(batch, lo, hi, key_shift, state, events)``, ``event(batch, i, key_shift, state, ts)``
    The program's ``ColumnarChunk`` of events [lo, hi) and ``CDCEvent`` of
    event ``i`` of ``batch``, keys shifted by ``key_shift``; ``state`` None
    keeps each event's registry state, else every event carries it;
    ``events`` is the chunk's lazily built ``CDCEvent`` sequence
    (``bench/lib/source.py``'s ``OpenLoopSource`` calls both).
``expected(d, batch, passes, control)``
    The reference's rows for ``passes``, a list of ``(take, key_shift)``:
    the first ``take`` events of ``batch``, keys shifted.  ``control``
    "none" is the reference; "bf16" the kind's control, which must fail.
``program_rows(d, arrays)``
    The program's rows, from ``TableSink.to_arrays()``.
``compare(want, got)``
    ``{"rows_missing", "rows_extra", "rows_wrong"}``, each a count with
    limit 0.  Rows are the kind's own; they have ``n``, their count.
``kernel_bytes(d, batch, polls)``
    Bytes the mapping of the polled chunks needs (``polls`` as
    ``OpenLoopSource.polls``), which ``densify_map_roofline`` reads.
``churn(d, w, rng, stream, churn, due, seconds)``, optional
    The window's events under a traffic file's ``churn`` block, and its
    in-band control items as ``(first, at, item)`` (see
    ``OpenLoopSource``).  A kind without it runs no churn traffic.
"""
