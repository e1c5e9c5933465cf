#!/usr/bin/env python
"""Multi-process replication smoke: kill the leader mid-stream, fail over
to a restarted leader, and prove zero dropped / zero duplicated rows.

    PYTHONPATH=src python scripts/replication_smoke.py [--fast]

Four acts, all real processes over real sockets (``repro.etl.replication``
CLI roles):

1. **oracle** -- one unreplicated process maps the whole chunk grid under
   the shared churn schedule (plus a Freeze/Thaw window): the canonical
   row set.
2. **cluster** -- a leader (slot 0) and two follower processes (slots 1-2)
   split the same grid.  The leader runs with ``--crash-after-chunks``
   fault injection: it ``_exit(17)``\\ s after *emitting* a chunk but
   before *checkpointing* it -- the worst spot, an orphaned output line.
3. **failover** -- the followers observe the dead transport (``LeaderLost``)
   and spin on reconnect; a new leader process resumes from the atomic
   (control_log offset, source offset) checkpoint under term 2, truncates
   the orphaned row line, backfills the followers' ledgers, and finishes
   the stream.
4. **audit** -- the merged (leader + follower) per-chunk rows must equal
   the oracle's bit-for-bit: same chunk set (nothing dropped), each chunk
   seen exactly once (nothing duplicated), same rows in each.

Exit 0 on success; non-zero with a diagnostic on any divergence.

This is a CPU drill of the control plane: every process it starts runs JAX
on the CPU backend (``JAX_PLATFORMS=cpu``).  The leader and followers run
side by side, and an accelerator belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": os.path.join(REPO, "src")
    + (os.pathsep + os.environ["PYTHONPATH"] if os.environ.get("PYTHONPATH") else ""),
}
CLI = [sys.executable, "-m", "repro.etl.replication"]


def free_port() -> int:
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def read_chunks(path: str) -> dict:
    """chunk index -> wire rows; duplicate indices within one file are a
    hard failure (a restart that forgot to truncate)."""
    out: dict = {}
    with open(path) as f:
        for line in f:
            if not line.strip():
                continue
            rec = json.loads(line)
            if rec["chunk"] in out:
                raise SystemExit(
                    f"FAIL: duplicated chunk {rec['chunk']} inside {path}"
                )
            out[rec["chunk"]] = rec["rows"]
    return out


def run_smoke(fast: bool) -> None:
    max_chunks, chunk_size = (9, 32) if fast else (12, 64)
    shared = [
        "--schemas", "5", "--seed", "7", "--stream-seed", "7",
        "--churn", "3", "--churn-first", "2", "--churn-every", "3",
        "--freeze-at", "3", "--thaw-at", "7",
        "--max-chunks", str(max_chunks), "--chunk-size", str(chunk_size),
    ]
    with tempfile.TemporaryDirectory() as tmp:
        oracle_out = os.path.join(tmp, "oracle.jsonl")
        subprocess.run(
            CLI + ["--role", "oracle", "--out", oracle_out] + shared,
            env=ENV, check=True, timeout=120,
        )
        oracle = read_chunks(oracle_out)
        print(f"oracle: {len(oracle)} chunks")

        port = free_port()
        ledger = os.path.join(tmp, "control.ledger")
        ckpt = os.path.join(tmp, "restart.ckpt")
        leader_out = os.path.join(tmp, "leader.jsonl")
        fol_outs = [os.path.join(tmp, f"f{s}.jsonl") for s in (1, 2)]

        followers = [
            subprocess.Popen(
                CLI + [
                    "--role", "follower", "--port", str(port),
                    "--slot", str(slot), "--instances", "3", "--out", out,
                ] + shared,
                env=ENV,
            )
            for slot, out in zip((1, 2), fol_outs)
        ]
        leader_cmd = CLI + [
            "--role", "leader", "--port", str(port), "--followers", "2",
            "--instances", "3", "--out", leader_out,
            "--ledger", ledger, "--checkpoint", ckpt,
        ] + shared
        crashed = subprocess.run(
            leader_cmd + ["--crash-after-chunks", "2"], env=ENV, timeout=120
        )
        if crashed.returncode != 17:
            raise SystemExit(
                f"FAIL: fault injection did not fire (leader rc "
                f"{crashed.returncode}, wanted 17)"
            )
        print("leader: crashed after 2 chunks (injected), restarting --resume")

        try:
            subprocess.run(
                leader_cmd + ["--resume"], env=ENV, check=True, timeout=120
            )
            for p in followers:
                if p.wait(timeout=120) != 0:
                    raise SystemExit(f"FAIL: follower exited rc {p.returncode}")
        finally:
            for p in followers:
                if p.poll() is None:
                    p.kill()

        got: dict = {}
        for path in [leader_out] + fol_outs:
            for h, rows in read_chunks(path).items():
                if h in got:
                    raise SystemExit(f"FAIL: chunk {h} emitted by two nodes")
                got[h] = rows

    dropped = sorted(set(oracle) - set(got))
    extra = sorted(set(got) - set(oracle))
    if dropped or extra:
        raise SystemExit(f"FAIL: dropped chunks {dropped}, extra chunks {extra}")
    bad = [h for h in oracle if got[h] != oracle[h]]
    if bad:
        raise SystemExit(f"FAIL: row divergence vs oracle in chunks {bad}")
    n = sum(len(v) for v in oracle.values())
    print(
        f"OK: leader kill + term-2 restart -- {n} rows over {len(oracle)} "
        "chunks, zero dropped, zero duplicated, bit-exact vs oracle"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true",
                    help="CI-sized grid (9 chunks of 32)")
    run_smoke(ap.parse_args().fast)


if __name__ == "__main__":
    main()
