"""repro.analysis: firing + clean-twin fixtures per rule, waiver semantics,
the repo self-check, and the two mutation checks the grep gates used to
carry (aliased app._fused reach-in; per-event dict walk in densify)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import RULES, analyze

REPO = Path(__file__).resolve().parents[1]


def _write(tmp_path: Path, rel: str, source: str) -> Path:
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(source)
    return p


def _rules_hit(report):
    return {f.rule for f in report.findings}


def _run(tmp_path, rel, source, **kw):
    _write(tmp_path, rel, source)
    return analyze([str(tmp_path)], **kw)


# ---------------------------------------------------------------- registry


def test_all_six_rules_registered():
    import repro.analysis.rules  # noqa: F401

    assert set(RULES) >= {
        "private-reach-in",
        "host-sync-in-hot-path",
        "hot-path-python-loop",
        "control-plane-purity",
        "jit-cache-hygiene",
        "kernel-ref-parity",
    }


# ---------------------------------------------------------- private-reach-in


def test_private_reach_in_fires_on_direct_access(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/bench.py",
        "app = METLApp(coord)\n"
        "n = app._fused\n",
    )
    assert "private-reach-in" in _rules_hit(rep)


def test_private_reach_in_fires_through_alias(tmp_path):
    # the case the old grep could never see: no literal 'app._' survives
    rep = _run(
        tmp_path,
        "benchmarks/bench.py",
        "shadow = METLApp(coord)\n"
        "mirror = shadow\n"
        "x = mirror._fused\n",
    )
    hits = [f for f in rep.findings if f.rule == "private-reach-in"]
    assert hits and "mirror._fused" in hits[0].message


def test_private_reach_in_backstop_any_receiver(tmp_path):
    # grep pattern 2 parity: known private names on an arbitrary receiver
    rep = _run(tmp_path, "benchmarks/b.py", "x = thing._dedup_window\n")
    assert "private-reach-in" in _rules_hit(rep)


def test_private_reach_in_clean_twin(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/bench.py",
        "app = METLApp(coord)\n"
        "info = app.engine.info()\n"
        "app.reset_dedup()\n",
    )
    assert "private-reach-in" not in _rules_hit(rep)


def test_private_reach_in_exempt_inside_owner(tmp_path):
    # the same access is legal from within repro.etl
    rep = _run(
        tmp_path,
        "src/repro/etl/helper.py",
        "app = METLApp(coord)\n"
        "n = app._fused\n",
    )
    assert "private-reach-in" not in _rules_hit(rep)


def test_private_reach_in_ignores_strings_and_comments(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/doc.py",
        '"""Docs mentioning app._fused and registry._state_id."""\n'
        "# app._fused is private\n"
        "x = 1\n",
    )
    assert "private-reach-in" not in _rules_hit(rep)


def test_private_registry_reach_in(tmp_path):
    rep = _run(
        tmp_path,
        "examples/demo.py",
        "registry = Registry(root)\n"
        "registry._state_id += 1\n",
    )
    assert "private-reach-in" in _rules_hit(rep)


# ----------------------------------------------------- host-sync-in-hot-path


_SYNC_FIRING = """\
import numpy as np

class Engine:
    def dispatch(self, dense):
        out = np.asarray(dense.vals)
        return out
"""

_SYNC_CLEAN = """\
import numpy as np

class Engine:
    def dispatch(self, dense):
        return launch(dense)

    def emit(self, handle):
        ov = np.asarray(handle.outputs[0])  # metl: allow[host-sync-in-hot-path] the engine sync point
        return ov
"""


def test_host_sync_fires_in_dispatch(tmp_path):
    rep = _run(tmp_path, "src/repro/etl/e.py", _SYNC_FIRING)
    assert "host-sync-in-hot-path" in _rules_hit(rep)


def test_host_sync_clean_twin_with_annotated_emit(tmp_path):
    rep = _run(tmp_path, "src/repro/etl/e.py", _SYNC_CLEAN)
    assert "host-sync-in-hot-path" not in _rules_hit(rep)
    assert any(f.rule == "host-sync-in-hot-path" for f, _ in rep.waived)


def test_host_sync_unannotated_emit_fires(tmp_path):
    src = _SYNC_CLEAN.replace(
        "  # metl: allow[host-sync-in-hot-path] the engine sync point", ""
    )
    rep = _run(tmp_path, "src/repro/etl/e.py", src)
    assert "host-sync-in-hot-path" in _rules_hit(rep)


def test_host_sync_scalar_readback_in_dispatch(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "def dispatch(dense):\n"
        "    s = float(dense.vals[0])\n"
        "    return s\n",
    )
    assert "host-sync-in-hot-path" in _rules_hit(rep)


def test_host_sync_early_readback_in_dispatch(tmp_path):
    """Starting the read-back in dispatch does not block; reading it does."""
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "import numpy as np\n"
        "\n"
        "def dispatch(dense):\n"
        "    handle = launch(dense)\n"
        "    handle.outputs[0].copy_to_host_async()\n"  # line 5
        "    handle.outputs[1].copy_to_host()\n"  # line 6
        "    np.asarray(handle.outputs[1])\n"  # line 7
        "    return handle\n",
    )
    hits = [f for f in rep.findings if f.rule == "host-sync-in-hot-path"]
    assert sorted(f.line for f in hits) == [6, 7]


def test_host_sync_out_of_scope_module(tmp_path):
    # same code outside repro.etl / repro.kernels is not this rule's business
    rep = _run(tmp_path, "scripts_dir/tool.py", _SYNC_FIRING)
    assert "host-sync-in-hot-path" not in _rules_hit(rep)


# ---------------------------------------------------- hot-path-python-loop


def test_hot_loop_fires_on_per_event_loop(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "def densify_chunk(plan, evs):\n"
        "    out = []\n"
        "    for ev in evs:\n"
        "        out.append(ev.key)\n"
        "    return out\n",
    )
    assert "hot-path-python-loop" in _rules_hit(rep)


def test_hot_loop_fires_on_payload_walk(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "def densify_chunk(plan, evs):\n"
        "    return [ev.payload() for ev in evs]\n",
    )
    assert "hot-path-python-loop" in _rules_hit(rep)


def test_hot_loop_clean_twin_per_column(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "def densify_chunk(plan, tri):\n"
        "    return {ov: gather(idx) for ov, idx in tri.by_column.items()}\n",
    )
    assert "hot-path-python-loop" not in _rules_hit(rep)


def test_hot_loop_mutation_dict_walk_in_densify_copy(tmp_path):
    """ISSUE mutation check: re-introduce a per-event dict walk into a copy
    of the real engines.py and the analyzer must flag it."""
    src = (REPO / "src/repro/etl/engines.py").read_text()
    src += (
        "\n\ndef _densify_chunk(plan, evs):\n"
        "    out = {}\n"
        "    for ev in evs:\n"
        "        for uid, val in ev.payload().items():\n"
        "            out[uid] = val\n"
        "    return out\n"
    )
    _write(tmp_path, "src/repro/etl/engines.py", src)
    rep = analyze([str(tmp_path)], select=["hot-path-python-loop"])
    assert not rep.ok
    appended_at = src[: src.index("def _densify_chunk")].count("\n") + 1
    assert any(f.line >= appended_at for f in rep.findings)


def test_private_reach_in_mutation_alias_in_benchmarks(tmp_path):
    """ISSUE mutation check: an aliased app._fused reach-in added to a
    benchmarks file fails the analyzer (the old grep stayed green)."""
    _write(
        tmp_path,
        "benchmarks/bench_new.py",
        "from repro.etl.metl import METLApp\n"
        "def run(coord):\n"
        "    application = METLApp(coord)\n"
        "    handle = application\n"
        "    return handle._fused\n",
    )
    rep = analyze([str(tmp_path)], select=["private-reach-in"])
    assert not rep.ok


# --------------------------------------------------- control-plane-purity


def test_control_purity_fires_outside_apply(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/x.py",
        "def sneak(event, registry):\n"
        "    event.mutate(registry)\n",
    )
    assert "control-plane-purity" in _rules_hit(rep)


def test_control_purity_clean_in_coordinator_apply(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/core/state.py",
        "class StateCoordinator:\n"
        "    def apply(self, event):\n"
        "        event.mutate(self.registry)\n",
    )
    assert "control-plane-purity" not in _rules_hit(rep)


def test_control_purity_unfrozen_event_fires(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/control.py",
        "import dataclasses\n"
        "class ControlEvent:\n"
        "    pass\n"
        "class SchemaEvolved(ControlEvent):\n"
        "    pass\n",
    )
    hits = [f for f in rep.findings if f.rule == "control-plane-purity"]
    assert hits and "SchemaEvolved" in hits[0].message


def test_control_purity_frozen_event_clean_and_transitive(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/control.py",
        "import dataclasses\n"
        "class ControlEvent:\n"
        "    pass\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class SchemaEvolved(ControlEvent):\n"
        "    schema_id: int\n"
        "class Grandchild(SchemaEvolved):\n"  # transitively an event, unfrozen
        "    pass\n",
    )
    hits = [f for f in rep.findings if f.rule == "control-plane-purity"]
    assert len(hits) == 1 and "Grandchild" in hits[0].message


# ----------------------------------------------------- jit-cache-hygiene


_JIT_FIRING = """\
import functools
import jax

@functools.lru_cache(maxsize=None)
def _program(mesh, axis: str):
    return jax.jit(lambda x: x)
"""

_JIT_CLEAN = """\
import functools
import jax
from jax.sharding import Mesh

@functools.lru_cache(maxsize=None)
def _program(mesh: Mesh, axis: str, fill: float):
    return jax.jit(lambda x: x)
"""


def test_jit_cache_fires_on_unannotated_param(tmp_path):
    rep = _run(tmp_path, "src/repro/kernels/p.py", _JIT_FIRING)
    hits = [f for f in rep.findings if f.rule == "jit-cache-hygiene"]
    assert hits and "'mesh'" in hits[0].message


def test_jit_cache_clean_twin(tmp_path):
    rep = _run(tmp_path, "src/repro/kernels/p.py", _JIT_CLEAN)
    assert "jit-cache-hygiene" not in _rules_hit(rep)


def test_jit_cache_fires_on_array_annotation(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/kernels/p.py",
        "import functools, jax\n"
        "@functools.lru_cache(maxsize=None)\n"
        "def _program(x: jax.Array):\n"
        "    return jax.jit(lambda v: v)\n",
    )
    assert "jit-cache-hygiene" in _rules_hit(rep)


def test_jit_cache_fires_on_star_args_and_list_call(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/kernels/p.py",
        _JIT_CLEAN + "\nprog = _program([1, 2], 'data', 0.0)\n",
    )
    hits = [f for f in rep.findings if f.rule == "jit-cache-hygiene"]
    assert hits and "unhashable literal" in hits[0].message


def test_jit_cache_ignores_uncached_jit(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/kernels/p.py",
        "import jax\n"
        "def build(mesh):\n"
        "    return jax.jit(lambda x: x)\n",
    )
    assert "jit-cache-hygiene" not in _rules_hit(rep)


# ----------------------------------------------------- kernel-ref-parity


_KERNEL = """\
from jax.experimental import pallas as pl

def my_map(x):
    return pl.pallas_call(None)(x)
"""


def test_kernel_parity_fires_without_twin(tmp_path):
    _write(tmp_path, "pkg/kernels/my_map.py", _KERNEL)
    _write(tmp_path, "pkg/kernels/ref.py", "def other_ref(x):\n    return x\n")
    (tmp_path / "tests").mkdir()
    rep = analyze([str(tmp_path / "pkg")])
    hits = [f for f in rep.findings if f.rule == "kernel-ref-parity"]
    assert hits and "my_map_ref" in hits[0].message


def test_kernel_parity_fires_without_parity_test(tmp_path):
    _write(tmp_path, "pkg/kernels/my_map.py", _KERNEL)
    _write(tmp_path, "pkg/kernels/ref.py", "def my_map_ref(x):\n    return x\n")
    # a test that uses the kernel but never consults the twin (the onehot bug)
    _write(tmp_path, "tests/test_k.py", "from pkg.kernels.my_map import my_map\n")
    rep = analyze([str(tmp_path / "pkg")])
    hits = [f for f in rep.findings if f.rule == "kernel-ref-parity"]
    assert hits and "my_map_ref()" in hits[0].message


def test_kernel_parity_clean_twin(tmp_path):
    _write(tmp_path, "pkg/kernels/my_map.py", _KERNEL)
    _write(tmp_path, "pkg/kernels/ref.py", "def my_map_ref(x):\n    return x\n")
    _write(
        tmp_path,
        "tests/test_k.py",
        "from pkg.kernels.my_map import my_map\n"
        "from pkg.kernels.ref import my_map_ref\n"
        "def test_parity():\n"
        "    assert my_map(1) == my_map_ref(1)\n",
    )
    rep = analyze([str(tmp_path / "pkg")])
    assert "kernel-ref-parity" not in _rules_hit(rep)


def test_kernel_parity_shard_variant_covered_by_base(tmp_path):
    _write(
        tmp_path,
        "pkg/kernels/my_map.py",
        _KERNEL + "\ndef my_map_shard(x):\n    return my_map(x)\n",
    )
    _write(tmp_path, "pkg/kernels/ref.py", "def my_map_ref(x):\n    return x\n")
    _write(
        tmp_path,
        "tests/test_k.py",
        "from pkg.kernels.my_map import my_map\n"
        "from pkg.kernels.ref import my_map_ref\n",
    )
    rep = analyze([str(tmp_path / "pkg")])
    assert "kernel-ref-parity" not in _rules_hit(rep)


# ------------------------------------------------------------- waivers


def test_waiver_line_below(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/b.py",
        "# metl: allow[private-reach-in] exercising the private shim on purpose\n"
        "x = thing._fused\n",
    )
    assert rep.ok and rep.waived


def test_waiver_on_def_covers_function(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "def densify_oracle(plan, evs):  # metl: allow[hot-path-python-loop] the oracle twin\n"
        "    a = [ev.key for ev in evs]\n"
        "    b = [ev.payload() for ev in evs]\n"
        "    return a, b\n",
    )
    assert rep.ok and len(rep.waived) >= 2


def test_waiver_does_not_leak_past_function(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "def densify_oracle(plan, evs):  # metl: allow[hot-path-python-loop] the oracle twin\n"
        "    return [ev.key for ev in evs]\n"
        "\n"
        "def densify_other(plan, evs):\n"
        "    return [ev.key for ev in evs]\n",
    )
    assert not rep.ok
    assert all(f.line >= 4 for f in rep.findings)


def test_waiver_without_reason_is_a_finding(tmp_path):
    rep = _run(tmp_path, "benchmarks/b.py", "x = thing._fused  # metl: allow[private-reach-in]\n")
    assert "bad-waiver" in _rules_hit(rep)


def test_waiver_unknown_rule_is_a_finding(tmp_path):
    rep = _run(tmp_path, "benchmarks/b.py", "x = 1  # metl: allow[no-such-rule] because\n")
    assert "bad-waiver" in _rules_hit(rep)


def test_waiver_only_covers_named_rule(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/b.py",
        "x = thing._fused  # metl: allow[hot-path-python-loop] wrong rule named\n",
    )
    assert "private-reach-in" in _rules_hit(rep)


def test_waiver_example_in_docstring_is_not_a_waiver(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/b.py",
        '"""Waive with ``# metl: allow[rule-id] reason``."""\nx = 1\n',
    )
    assert rep.ok


# ------------------------------------------------------- select / ignore


def test_select_and_ignore(tmp_path):
    _write(
        tmp_path,
        "src/repro/etl/e.py",
        "import numpy as np\n"
        "def dispatch(dense):\n"
        "    return np.asarray(dense)\n"
        "def densify_x(plan, evs):\n"
        "    return [ev.key for ev in evs]\n",
    )
    both = analyze([str(tmp_path)])
    assert _rules_hit(both) == {"host-sync-in-hot-path", "hot-path-python-loop"}
    only = analyze([str(tmp_path)], select=["host-sync-in-hot-path"])
    assert _rules_hit(only) == {"host-sync-in-hot-path"}
    without = analyze([str(tmp_path)], ignore=["host-sync-in-hot-path"])
    assert _rules_hit(without) == {"hot-path-python-loop"}
    with pytest.raises(ValueError):
        analyze([str(tmp_path)], select=["no-such-rule"])


def test_parse_error_is_a_finding(tmp_path):
    rep = _run(tmp_path, "benchmarks/b.py", "def broken(:\n")
    assert "parse-error" in _rules_hit(rep)


# ------------------------------------------------------------- self-check


def test_repo_tree_is_clean():
    """The shipped tree passes its own analyzer (what ci.sh asserts)."""
    rep = analyze(
        [str(REPO / "src"), str(REPO / "benchmarks"), str(REPO / "examples")]
    )
    assert rep.ok, "\n".join(f.render() for f in rep.findings)
    # the deliberate engine sync points and the dict-walk oracle are waived,
    # with reasons, not invisible
    assert any(f.rule == "host-sync-in-hot-path" for f, _ in rep.waived)
    assert any(f.rule == "hot-path-python-loop" for f, _ in rep.waived)
    assert all(w.reason for _, w in rep.waived)


# ------------------------------------------------------------------- CLI


def _cli(*args, cwd=None):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True,
        text=True,
        cwd=cwd or REPO,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"},
    )


def test_cli_clean_tree_exits_zero_and_writes_report(tmp_path):
    report_file = tmp_path / "ANALYSIS.json"
    proc = _cli("src", "benchmarks", "examples", "--output", "json",
                "--report", str(report_file))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is True and payload["n_files"] > 50
    assert json.loads(report_file.read_text())["ok"] is True


def test_cli_findings_exit_one(tmp_path):
    _write(tmp_path, "benchmarks/b.py", "x = thing._fused\n")
    proc = _cli(str(tmp_path))
    assert proc.returncode == 1
    assert "[private-reach-in]" in proc.stdout


def test_cli_usage_errors_exit_two(tmp_path):
    assert _cli().returncode == 2
    assert _cli(str(tmp_path), "--select", "no-such-rule").returncode == 2


def test_cli_list_rules():
    proc = _cli("--list-rules")
    assert proc.returncode == 0
    for rid in RULES or ["private-reach-in"]:
        assert rid in proc.stdout


# ------------------------------------------------------ donated-buffer-reuse


_DONATE_FIRING = """\
import functools
import jax

@functools.lru_cache(maxsize=None)
def _prog(donate: bool):
    return jax.jit(lambda p: p * 2, donate_argnums=(0,) if donate else ())

def apply_packed(packed):
    out = _prog(True)(packed)
    return packed.sum() + out
"""


def test_donated_reuse_fires_through_factory(tmp_path):
    rep = _run(tmp_path, "src/repro/kernels/p.py", _DONATE_FIRING)
    assert _rules_hit(rep) == {"donated-buffer-reuse"}


def test_donated_reuse_clean_twin_rebind(tmp_path):
    src = _DONATE_FIRING.replace(
        "    out = _prog(True)(packed)\n    return packed.sum() + out\n",
        "    packed = _prog(True)(packed)\n    return packed.sum()\n",
    )
    rep = _run(tmp_path, "src/repro/kernels/p.py", src)
    assert "donated-buffer-reuse" not in _rules_hit(rep)


def test_donated_reuse_module_level_program(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/kernels/p.py",
        "import jax\n"
        "f = jax.jit(lambda x: x + 1, donate_argnums=(0,))\n"
        "def run(buf):\n"
        "    out = f(buf)\n"
        "    return buf.sum() + out\n",
    )
    assert _rules_hit(rep) == {"donated-buffer-reuse"}


def test_donated_reuse_sees_through_import_alias(tmp_path):
    # the wrapper donates its param via the fixpoint; the caller in another
    # module reaches it through an import alias
    _write(tmp_path, "src/repro/kernels/ops.py", _DONATE_FIRING.replace(
        "def apply_packed(packed):\n"
        "    out = _prog(True)(packed)\n"
        "    return packed.sum() + out\n",
        "def apply_packed(packed):\n"
        "    return _prog(True)(packed)\n",
    ))
    _write(
        tmp_path,
        "src/repro/etl/e.py",
        "from repro.kernels.ops import apply_packed as launch\n"
        "def consume(buf):\n"
        "    out = launch(buf)\n"
        "    return buf[0], out\n",
    )
    rep = analyze([str(tmp_path)], select=["donated-buffer-reuse"])
    hits = [f for f in rep.findings if f.rule == "donated-buffer-reuse"]
    assert hits and "'buf'" in hits[0].message and "consume" in hits[0].message


def test_donated_reuse_mutation_in_engines_copy(tmp_path):
    """ISSUE mutation check: a read of the donated packed buffer after the
    real dmm_apply_columnar callsite (copied from engines.py, resolved
    cross-module into ops.py) must fire."""
    for rel in ("src/repro/etl/engines.py", "src/repro/kernels/ops.py"):
        _write(tmp_path, rel, (REPO / rel).read_text())
    src = (REPO / "src/repro/etl/engines.py").read_text()
    src += (
        "\n\ndef _evil_reuse(dense, fused):\n"
        "    outputs = dmm_apply_columnar(\n"
        "        dense.packed,\n"
        "        fused.uid_slot_dev,\n"
        "        fused.uid_col_dev,\n"
        "        fused.src2d,\n"
        "        n_items=dense.n_items,\n"
        "        n_events=dense.n_events,\n"
        "        n_rows=dense.n_rows,\n"
        "        k=dense.k,\n"
        "    )\n"
        "    return dense.packed.sum(), outputs\n"
    )
    _write(tmp_path, "src/repro/etl/engines.py", src)
    rep = analyze([str(tmp_path)], select=["donated-buffer-reuse"])
    assert not rep.ok
    assert all(f.rule == "donated-buffer-reuse" for f in rep.findings)
    assert any("dense.packed" in f.message for f in rep.findings)


# ----------------------------------------------------- single-writer-control


def test_single_writer_fires_outside_apply(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/x.py",
        "def sneak(coord, ev):\n"
        "    coord.control_log.append(ev)\n",
    )
    assert _rules_hit(rep) == {"single-writer-control"}


def test_single_writer_clean_in_apply_and_its_helper(tmp_path):
    # _log is only ever called from apply: the call graph resolves the
    # wrapper, no finding
    rep = _run(
        tmp_path,
        "src/repro/core/state.py",
        "class StateCoordinator:\n"
        "    def apply(self, event):\n"
        "        self._log(event)\n"
        "    def _log(self, event):\n"
        "        self.control_log.append(event)\n",
    )
    assert "single-writer-control" not in _rules_hit(rep)


def test_single_writer_open_helper_fires(tmp_path):
    # the same helper with a second, non-apply caller is an open write path
    rep = _run(
        tmp_path,
        "src/repro/core/state.py",
        "class StateCoordinator:\n"
        "    def apply(self, event):\n"
        "        self._log(event)\n"
        "    def _log(self, event):\n"
        "        self.control_log.append(event)\n"
        "def backdoor(coord, ev):\n"
        "    coord._log(ev)\n",
    )
    assert "single-writer-control" in _rules_hit(rep)


def test_single_writer_replica_apply_fires(tmp_path):
    # in the replication modules, coordinator.apply outside LeaderNode is a
    # follower-side write the replicated log never shipped
    rep = _run(
        tmp_path,
        "src/repro/etl/replication.py",
        "class FollowerNode:\n"
        "    def catch_up(self, event):\n"
        "        self.coordinator.apply(event)\n",
    )
    assert "single-writer-control" in _rules_hit(rep)


def test_single_writer_replica_apply_clean_twins(tmp_path):
    # clean twin 1: the same call inside LeaderNode (the leader path owns
    # apply); clean twin 2: follower replay through replay_control_log
    rep = _run(
        tmp_path,
        "src/repro/etl/replication.py",
        "from repro.etl.control import replay_control_log\n"
        "class LeaderNode:\n"
        "    def apply(self, event):\n"
        "        self.coordinator.apply(event)\n"
        "class FollowerNode:\n"
        "    def advance_to(self, due):\n"
        "        replay_control_log(due, coordinator=self.coordinator)\n",
    )
    assert "single-writer-control" not in _rules_hit(rep)


def test_single_writer_replica_scope_is_module_bound(tmp_path):
    # the leader-only apply restriction binds to the replication modules;
    # ordinary etl code calling coordinator.apply stays clean
    rep = _run(
        tmp_path,
        "src/repro/etl/other.py",
        "def drive(coordinator, event):\n"
        "    coordinator.apply(event)\n",
    )
    assert "single-writer-control" not in _rules_hit(rep)


def test_single_writer_replication_module_is_clean():
    """The shipped replication/transport modules pass their own rule: only
    LeaderNode applies, followers replay."""
    rep = analyze(
        [
            str(REPO / "src/repro/etl/replication.py"),
            str(REPO / "src/repro/etl/transport.py"),
        ],
        select=["single-writer-control"],
    )
    assert rep.ok, "\n".join(f.render() for f in rep.findings)


def test_single_writer_mutation_in_state_copy(tmp_path):
    """ISSUE mutation check: an out-of-apply control_log append added to a
    copy of the real state.py must fire."""
    src = (REPO / "src/repro/core/state.py").read_text()
    src += (
        "\n\ndef sneak_record(coordinator, record):\n"
        "    coordinator.control_log.append(record)\n"
    )
    _write(tmp_path, "src/repro/core/state.py", src)
    rep = analyze([str(tmp_path)], select=["single-writer-control"])
    assert not rep.ok
    assert all(f.rule == "single-writer-control" for f in rep.findings)


# --------------------------------------------------------- epoch-pin-escape


def test_epoch_pin_fires_on_unpinned_chunk(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "def make(vals, mask):\n"
        "    return DenseChunk(vals=vals, mask=mask)\n",
    )
    assert _rules_hit(rep) == {"epoch-pin-escape"}


def test_epoch_pin_fires_on_read_across_mutation(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "def consume_guarded(coord, plan, evs, ev):\n"
        "    dense = densify(plan, evs)\n"
        "    coord.apply(ev)\n"
        "    return dense.plan\n",
    )
    assert _rules_hit(rep) == {"epoch-pin-escape"}


def test_epoch_pin_clean_twin_redensify(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "def consume_ok(coord, plan, evs, ev):\n"
        "    dense = densify(plan, evs)\n"
        "    rows = dense.plan\n"
        "    coord.apply(ev)\n"
        "    dense = densify(plan, evs)\n"
        "    return dense.plan, rows\n",
    )
    assert "epoch-pin-escape" not in _rules_hit(rep)


def test_epoch_pin_mutation_dropped_pin_in_engines_copy(tmp_path):
    """ISSUE mutation check: dropping the plan pin from the real DenseChunk
    construction (copied engines.py) must fire."""
    src = (REPO / "src/repro/etl/engines.py").read_text()
    mutated = src.replace(
        "return DenseChunk(\n        plan=plan,",
        "return DenseChunk(\n        plan=None,",
        1,
    )
    assert mutated != src, "engines.py DenseChunk callsite moved; update test"
    _write(tmp_path, "src/repro/etl/engines.py", mutated)
    rep = analyze([str(tmp_path)], select=["epoch-pin-escape"])
    assert not rep.ok
    assert all(f.rule == "epoch-pin-escape" for f in rep.findings)


# ------------------------------------------------------- transfer-accounting


def test_transfer_accounting_fires_on_reachable_device_put(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "import jax\n"
        "def dispatch(dense):\n"
        "    return _stage(dense)\n"
        "def _stage(dense):\n"
        "    return jax.device_put(dense.vals)\n",
    )
    assert _rules_hit(rep) == {"transfer-accounting"}


def test_transfer_accounting_clean_outside_hot_path(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "import jax\n"
        "def prepare_offline(dense):\n"
        "    return jax.device_put(dense.vals)\n",
    )
    assert "transfer-accounting" not in _rules_hit(rep)


def test_transfer_accounting_waived_single_site(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/e.py",
        "import jax.numpy as jnp\n"
        "def dispatch(dense):\n"
        "    return _to_device(dense.vals)\n"
        "def _to_device(*arrays):  # metl: allow[transfer-accounting] the ONE accounted site\n"
        "    return tuple(jnp.asarray(a) for a in arrays)\n",
    )
    assert "transfer-accounting" not in _rules_hit(rep)
    assert any(f.rule == "transfer-accounting" for f, _ in rep.waived)


# ------------------------------------------------------------ unused-waiver


def test_unused_waiver_fires_on_stale_waiver(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/b.py",
        "x = 1  # metl: allow[private-reach-in] excused code is long gone\n",
    )
    assert _rules_hit(rep) == {"unused-waiver"}


def test_unused_waiver_clean_when_suppressing(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/b.py",
        "x = thing._fused  # metl: allow[private-reach-in] exercising the shim\n",
    )
    assert rep.ok and rep.waived


def test_unused_waiver_not_judged_when_rule_not_selected(tmp_path):
    # a hot-path waiver can't be judged stale by a sweep that never ran the
    # hot-path rule (the scoped tests/ sweep in ci.sh)
    _write(
        tmp_path,
        "src/repro/etl/e.py",
        "x = 1  # metl: allow[hot-path-python-loop] judged only when the rule runs\n",
    )
    scoped = analyze(
        [str(tmp_path)],
        select=["private-reach-in", "bad-waiver", "unused-waiver"],
    )
    assert scoped.ok
    full = analyze([str(tmp_path)])
    assert _rules_hit(full) == {"unused-waiver"}


def test_unused_waiver_reasonless_is_bad_waiver_only(tmp_path):
    rep = _run(tmp_path, "benchmarks/b.py", "x = 1  # metl: allow[private-reach-in]\n")
    assert _rules_hit(rep) == {"bad-waiver"}


def test_unused_waiver_cannot_be_waived(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/b.py",
        "# metl: allow[unused-waiver] trying to excuse the audit itself\n"
        "x = 1  # metl: allow[private-reach-in] stale\n",
    )
    assert "unused-waiver" in _rules_hit(rep)


# ------------------------------------------------------------- registry (12)


def test_all_twelve_rules_registered():
    import repro.analysis.rules  # noqa: F401

    assert set(RULES) >= {
        "private-reach-in",
        "host-sync-in-hot-path",
        "hot-path-python-loop",
        "control-plane-purity",
        "jit-cache-hygiene",
        "kernel-ref-parity",
        "donated-buffer-reuse",
        "single-writer-control",
        "epoch-pin-escape",
        "transfer-accounting",
        "plan-publish-single-site",
        "bad-waiver",
        "unused-waiver",
    }


# ------------------------------------------------------------- CLI (github)


def test_cli_github_output_renders_error_annotations(tmp_path):
    _write(tmp_path, "benchmarks/b.py", "x = thing._fused\n")
    proc = _cli(str(tmp_path), "--output", "github")
    assert proc.returncode == 1
    assert "::error file=" in proc.stdout
    assert "title=repro.analysis[private-reach-in]" in proc.stdout


def test_cli_github_output_clean_tree(tmp_path):
    _write(tmp_path, "benchmarks/b.py", "x = 1\n")
    proc = _cli(str(tmp_path), "--output", "github")
    assert proc.returncode == 0
    assert "::error" not in proc.stdout
    assert "repro.analysis: OK" in proc.stdout

# ------------------------------------------------- plan-publish-single-site


def test_plan_publish_fires_on_direct_compile(tmp_path):
    # compile_dpm stays free (benchmarks A/B the host compacted form);
    # the fused lowering is the single-site contract
    rep = _run(
        tmp_path,
        "benchmarks/bench.py",
        "from repro.core.dmm_jax import compile_dpm, compile_fused\n"
        "plan = compile_fused(compile_dpm(dpm, reg), reg)\n",
    )
    hits = [f for f in rep.findings if f.rule == "plan-publish-single-site"]
    assert len(hits) == 1 and "compile_fused" in hits[0].message


def test_plan_publish_fires_through_import_alias(tmp_path):
    # no restricted name survives at the call site: resolution through the
    # module's import table catches the alias
    rep = _run(
        tmp_path,
        "src/repro/etl/engines.py",
        "from repro.core.dmm_jax import splice_fused as sf\n"
        "plan = sf(old, compiled, reg, touched)\n",
    )
    assert "plan-publish-single-site" in _rules_hit(rep)


def test_plan_publish_fires_on_handmade_publish_event(tmp_path):
    rep = _run(
        tmp_path,
        "src/repro/etl/cluster.py",
        "from .control import PlanPublished\n"
        "def announce(coord, n):\n"
        "    coord.apply(PlanPublished(epoch=n, state=0, kind='fused',\n"
        "                              n_blocks=0, bytes_resident=0,\n"
        "                              incremental=False, touched_columns=0,\n"
        "                              rebuild_s=0.0))\n",
    )
    hits = [f for f in rep.findings if f.rule == "plan-publish-single-site"]
    assert hits and "PlanPublished" in hits[0].message


def test_plan_publish_clean_twin_manager_lease(tmp_path):
    rep = _run(
        tmp_path,
        "benchmarks/bench.py",
        "from repro.core.dmm_jax import compile_dpm\n"
        "from repro.etl import METLApp, PlanManager\n"
        "mgr = PlanManager(kind='fused', coordinator=coord)\n"
        "app = METLApp(coord, plan_manager=mgr)\n"
        "lease = mgr.acquire(snap, reg)\n"
        "compiled = compile_dpm(dpm, reg)\n"
        "ok = isinstance(lease.plan, FusedDMM)\n",
    )
    assert "plan-publish-single-site" not in _rules_hit(rep)


def test_plan_publish_exempt_inside_owners(tmp_path):
    _write(
        tmp_path,
        "src/repro/etl/plan.py",
        "from repro.core.dmm_jax import compile_fused\n"
        "def _build(compiled, reg):\n"
        "    return compile_fused(compiled, reg)\n",
    )
    _write(
        tmp_path,
        "src/repro/core/dmm_jax.py",
        "def compile_fused(compiled, reg):\n"
        "    return FusedDMM(state=0)\n",
    )
    rep = analyze([str(tmp_path)], select=["plan-publish-single-site"])
    assert rep.ok, "\n".join(f.render() for f in rep.findings)


def test_plan_publish_mutation_in_engines_copy(tmp_path):
    """ISSUE mutation check: an engine quietly lowering its own fused plan
    (the pre-PR-9 shape) in a copy of the real engines.py must fire."""
    src = (REPO / "src/repro/etl/engines.py").read_text()
    src += (
        "\n\ndef sneak_compile(compiled, registry):\n"
        "    from ..core.dmm_jax import compile_fused\n"
        "    return compile_fused(compiled, registry)\n"
    )
    _write(tmp_path, "src/repro/etl/engines.py", src)
    rep = analyze([str(tmp_path)], select=["plan-publish-single-site"])
    assert not rep.ok
    assert all(f.rule == "plan-publish-single-site" for f in rep.findings)
