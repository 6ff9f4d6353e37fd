"""xgtpu-lint v3: dataflow-aware rules XGT013-XGT015, contract rules
XGT016/XGT017, SARIF output, and the DonationGuard runtime twin
(ANALYSIS.md §v3, analysis/dataflow.py + analysis/contracts.py).

Layers:

1. **fixture snippets** — each dataflow rule fires on its known-bad
   snippet (including the ISSUE's pinned cases: the aliased donated
   buffer MUST fail, the carry rebind MUST pass, a psum over a renamed
   mesh axis MUST fail) and is silenced by ``# xgtpu: disable=``;
2. **contract mini-trees** — XGT016 (exit-code registry) and XGT017
   (event-name drift) fire on bad trees and stay quiet on good twins,
   and their ``exit_codes``/``events`` inventory sections round-trip;
3. **enforcement** — the tier-1 gate: the whole repo is clean under
   XGT013-XGT017 with an EMPTY baseline (debt was fixed, not
   baselined);
4. **SARIF** — ``--sarif`` emits valid SARIF 2.1.0 whose results
   round-trip against ``--json`` (same findings, same exit contract);
5. **runtime twin** — DonationGuard gives CPU the device's donation
   semantics, and an integration run drives the REAL fused
   ``_scan_rounds`` dispatch under it: the tree's carry discipline
   holds in execution, not just in the AST.

Everything except the DonationGuard integration test is pure
stdlib-AST work; that one runs a tiny CPU training job.
"""

import json
import os

import numpy as np
import pytest

from xgboost_tpu.analysis import analyze_source
from xgboost_tpu.analysis.__main__ import main as lint_main
from xgboost_tpu.analysis.contracts import ContractEngine
from xgboost_tpu.analysis.rules import rules_by_code

PKG_DIR = os.path.dirname(os.path.abspath(__import__(
    "xgboost_tpu").__file__))
REPO_ROOT = os.path.dirname(PKG_DIR)
TOOLS_DIR = os.path.join(REPO_ROOT, "tools")


def codes(src, only, path="xgboost_tpu/models/gbtree.py"):
    """Codes from ``only`` firing on a snippet (rule-filtered, so a
    fixture for one rule cannot leak hits from another)."""
    active, _ = analyze_source(src, path=path, rules=rules_by_code(only))
    return sorted({f.rule for f in active})


def findings(src, only, path="xgboost_tpu/models/gbtree.py"):
    active, _ = analyze_source(src, path=path, rules=rules_by_code(only))
    return active


def suppressed(src, only, path="xgboost_tpu/models/gbtree.py"):
    _, sup = analyze_source(src, path=path, rules=rules_by_code(only))
    return sorted({f.rule for f in sup})


# ----------------------------------------------------------------- XGT013
class TestUseAfterDonate:
    def test_read_after_donate_fires(self):
        bad = ("import jax\n"
               "fn = jax.jit(impl, donate_argnums=(0,))\n"
               "def run(m):\n"
               "    out = fn(m, 3)\n"
               "    return m.sum()\n")
        fs = findings(bad, ["XGT013"])
        assert [f.rule for f in fs] == ["XGT013"]
        assert fs[0].line == 5  # anchored at the dead READ, not the call

    def test_carry_rebind_must_pass(self):
        good = ("import jax\n"
                "fn = jax.jit(impl, donate_argnums=(0,))\n"
                "def run(m):\n"
                "    m = fn(m, 3)\n"
                "    return m\n")
        assert codes(good, ["XGT013"]) == []

    def test_aliased_donated_buffer_must_fail(self):
        # the ISSUE's pinned MUST-FAIL: the carry rebind revives the
        # NAME, but `keep` still points at the dead buffer
        bad = ("import jax\n"
               "fn = jax.jit(impl, donate_argnums=(0,))\n"
               "def run(m):\n"
               "    keep = m\n"
               "    m = fn(m, 3)\n"
               "    return keep.sum()\n")
        fs = findings(bad, ["XGT013"])
        assert [f.rule for f in fs] == ["XGT013"]
        assert "alias" in fs[0].message

    def test_loop_without_rebind_fires(self):
        bad = ("import jax\n"
               "fn = jax.jit(impl, donate_argnums=(0,))\n"
               "def run(m):\n"
               "    for i in range(3):\n"
               "        out = fn(m, i)\n"
               "    return out\n")
        assert codes(bad, ["XGT013"]) == ["XGT013"]

    def test_loop_with_carry_rebind_is_clean(self):
        good = ("import jax\n"
                "fn = jax.jit(impl, donate_argnums=(0,))\n"
                "def run(m):\n"
                "    for i in range(3):\n"
                "        m = fn(m, i)\n"
                "    return m\n")
        assert codes(good, ["XGT013"]) == []

    def test_redefinition_revives_the_name(self):
        good = ("import jax\n"
                "fn = jax.jit(impl, donate_argnums=(0,))\n"
                "def run(m):\n"
                "    out = fn(m, 3)\n"
                "    m = out * 2\n"
                "    return m.sum()\n")
        assert codes(good, ["XGT013"]) == []

    def test_gbtree_shape_conditional_wrapper_and_tuple(self):
        # the real call shape: partial(jax.jit,..)(impl) definition,
        # `scan = donated if flag else plain` selection, tuple-wrapped
        # pytree at a donated position, results bound to fresh names,
        # donated names never read again
        good = (
            "import functools, jax\n"
            "_donated = functools.partial(\n"
            "    jax.jit, static_argnames=('k',),\n"
            "    donate_argnums=(1, 3))(impl)\n"
            "_plain = jax.jit(impl)\n"
            "def run(data, margin, emargins, flag):\n"
            "    scan = _donated if flag else _plain\n"
            "    margin_f, eouts = scan(data, margin, 0,\n"
            "                           tuple(emargins), k=4)\n"
            "    return margin_f, eouts\n")
        assert codes(good, ["XGT013"]) == []
        bad = good.replace("    return margin_f, eouts\n",
                           "    return margin.sum()\n")
        assert codes(bad, ["XGT013"]) == ["XGT013"]

    def test_suppression_silences(self):
        bad = ("import jax\n"
               "fn = jax.jit(impl, donate_argnums=(0,))\n"
               "def run(m):\n"
               "    out = fn(m, 3)\n"
               "    return m.sum()  # xgtpu: disable=XGT013\n")
        assert codes(bad, ["XGT013"]) == []
        assert suppressed(bad, ["XGT013"]) == ["XGT013"]


# ----------------------------------------------------------------- XGT014
class TestImpureTracedScope:
    def test_event_time_print_in_jit_fire(self):
        bad = ("import jax, time\n"
               "from xgboost_tpu.obs import trace\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    trace.event('train.step', n=1)\n"
               "    t = time.time()\n"
               "    print(x)\n"
               "    return x * 2\n")
        fs = findings(bad, ["XGT014"])
        assert len(fs) == 3 and {f.rule for f in fs} == {"XGT014"}

    def test_global_mutation_fires(self):
        bad = ("import jax\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    global N\n"
               "    N = 1\n"
               "    return x\n")
        assert codes(bad, ["XGT014"]) == ["XGT014"]

    def test_np_asarray_on_traced_fires_static_kwonly_clean(self):
        src = ("import jax\n"
               "import numpy as np\n"
               "@jax.jit\n"
               "def step(x, *, k):\n"
               "    a = np.asarray(x)\n"
               "    b = np.asarray(k)\n"
               "    return a\n")
        fs = findings(src, ["XGT014"])
        assert len(fs) == 1 and fs[0].line == 5  # only the traced arg

    def test_jax_debug_is_exempt(self):
        good = ("import jax\n"
                "@jax.jit\n"
                "def step(x):\n"
                "    jax.debug.print('x={x}', x=x)\n"
                "    return x * 2\n")
        assert codes(good, ["XGT014"]) == []

    def test_host_side_code_is_clean(self):
        good = ("import time\n"
                "from xgboost_tpu.obs import trace\n"
                "def host(x):\n"
                "    trace.event('train.done', n=1)\n"
                "    print(x, time.time())\n")
        assert codes(good, ["XGT014"]) == []

    def test_scan_body_is_traced(self):
        # passed to lax.scan by name, not jit-decorated: still traced,
        # and so is a def nested inside it
        bad = ("import jax\n"
               "def train(xs):\n"
               "    def body(carry, x):\n"
               "        print(x)\n"
               "        return carry, x\n"
               "    return jax.lax.scan(body, 0, xs)\n")
        assert codes(bad, ["XGT014"]) == ["XGT014"]

    def test_suppression_silences(self):
        bad = ("import jax\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    print(x)  # xgtpu: disable=XGT014 -- trace-time probe\n"
               "    return x\n")
        assert codes(bad, ["XGT014"]) == []
        assert suppressed(bad, ["XGT014"]) == ["XGT014"]


# ----------------------------------------------------------------- XGT015
SHARD_SRC = ("import jax\n"
             "from jax.sharding import PartitionSpec as P\n"
             "DATA_AXIS = 'data'\n"
             "def body(x):\n"
             "    return jax.lax.psum(x, {axis})\n"
             "def run(mesh, x):\n"
             "    f = shard_map(body, mesh=mesh,\n"
             "                  in_specs=(P(DATA_AXIS),),\n"
             "                  out_specs=P(DATA_AXIS))\n"
             "    return f(x)\n")


class TestCollectiveAxisDiscipline:
    def test_renamed_axis_must_fail(self):
        # the ISSUE's pinned MUST-FAIL: psum over an axis name the
        # enclosing shard_map's specs never mention
        fs = findings(SHARD_SRC.format(axis="'batch'"), ["XGT015"])
        assert [f.rule for f in fs] == ["XGT015"]
        assert "'batch'" in fs[0].message

    def test_constant_resolved_axis_passes(self):
        assert codes(SHARD_SRC.format(axis="DATA_AXIS"), ["XGT015"]) == []
        assert codes(SHARD_SRC.format(axis="'data'"), ["XGT015"]) == []

    def test_imported_constant_matches_symbolically(self):
        # DATA_AXIS imported, not defined in-file: both sides
        # canonicalize to $DATA_AXIS and match
        src = SHARD_SRC.replace("DATA_AXIS = 'data'\n", "")
        assert codes("from xgboost_tpu.parallel.mesh import DATA_AXIS\n"
                     + src.format(axis="DATA_AXIS"), ["XGT015"]) == []

    def test_param_axis_is_skipped(self):
        # axis name flowing in as a parameter is a config seam the
        # static rule cannot judge — skipped, not guessed
        src = ("import jax\n"
               "from jax.sharding import PartitionSpec as P\n"
               "def body(x, *, axis_name):\n"
               "    return jax.lax.psum(x, axis_name)\n"
               "def run(mesh, x):\n"
               "    f = shard_map(body, mesh=mesh, in_specs=(P('data'),),\n"
               "                  out_specs=P('data'))\n"
               "    return f(x)\n")
        assert codes(src, ["XGT015"]) == []

    def test_collective_under_traced_branch_fires(self):
        bad = ("import jax\n"
               "@jax.jit\n"
               "def step(x):\n"
               "    if x.sum() > 0:\n"
               "        x = jax.lax.psum(x, 'data')\n"
               "    return x\n")
        fs = findings(bad, ["XGT015"])
        assert [f.rule for f in fs] == ["XGT015"]
        assert "trace time" in fs[0].message

    def test_static_tests_are_exempt(self):
        good = ("import jax\n"
                "@jax.jit\n"
                "def step(x, *, use_dp):\n"
                "    if use_dp and x.ndim > 1:\n"
                "        x = jax.lax.psum(x, 'data')\n"
                "    if x is None:\n"
                "        return x\n"
                "    return x\n")
        assert codes(good, ["XGT015"]) == []

    def test_suppression_silences(self):
        bad = SHARD_SRC.format(axis="'batch'").replace(
            "psum(x, 'batch')",
            "psum(x, 'batch')  # xgtpu: disable=XGT015")
        assert codes(bad, ["XGT015"]) == []
        assert suppressed(bad, ["XGT015"]) == ["XGT015"]


# ----------------------------------------------------------------- XGT016
RC_SRC = ("FENCE_RC = 143\n"
          "HOST_LOSS_RC = 144\n")


def contract_run(tmp_path, codes_):
    eng = ContractEngine(str(tmp_path), codes=codes_)
    return eng.run()[0], eng


class TestExitCodeRegistry:
    def _registry(self, tmp_path):
        (tmp_path / "reliability").mkdir()
        (tmp_path / "reliability" / "rc.py").write_text(RC_SRC)

    def test_magic_literal_for_registered_code_fires(self, tmp_path):
        self._registry(tmp_path)
        (tmp_path / "w.py").write_text(
            "import os\ndef die():\n    os._exit(143)\n")
        act, _ = contract_run(tmp_path, {"XGT016"})
        assert len(act) == 1 and "FENCE_RC" in act[0].message

    def test_unregistered_protocol_code_fires(self, tmp_path):
        self._registry(tmp_path)
        (tmp_path / "w.py").write_text(
            "import sys\ndef die():\n    sys.exit(77)\n")
        act, _ = contract_run(tmp_path, {"XGT016"})
        assert len(act) == 1 and "unregistered" in act[0].message

    def test_generic_posix_codes_exempt(self, tmp_path):
        self._registry(tmp_path)
        (tmp_path / "w.py").write_text(
            "import sys\n"
            "def die(bad):\n"
            "    sys.exit(2 if bad else 0)\n"
            "def fail():\n"
            "    sys.exit(1)\n")
        act, _ = contract_run(tmp_path, {"XGT016"})
        assert act == []

    def test_rc_constant_outside_registry_fires(self, tmp_path):
        self._registry(tmp_path)
        (tmp_path / "w.py").write_text("MY_FAIL_RC = 99\n")
        act, _ = contract_run(tmp_path, {"XGT016"})
        assert len(act) == 1 and "outside the registry" in act[0].message

    def test_returncode_compare_against_literal_fires(self, tmp_path):
        self._registry(tmp_path)
        (tmp_path / "w.py").write_text(
            "def classify(p):\n"
            "    if p.returncode == 143:\n"
            "        return 'fence'\n"
            "    return 'ok' if p.returncode == 0 else 'crash'\n")
        act, _ = contract_run(tmp_path, {"XGT016"})
        # only the registered 143 fires; rc == 0 is out of scope
        assert len(act) == 1 and "143" in act[0].message

    def test_symbolic_usage_is_clean(self, tmp_path):
        self._registry(tmp_path)
        (tmp_path / "w.py").write_text(
            "import os\n"
            "from reliability.rc import FENCE_RC\n"
            "def die():\n    os._exit(FENCE_RC)\n"
            "def classify(p):\n    return p.returncode == FENCE_RC\n")
        act, _ = contract_run(tmp_path, {"XGT016"})
        assert act == []

    def test_duplicate_registration_fires(self, tmp_path):
        (tmp_path / "reliability").mkdir()
        (tmp_path / "reliability" / "rc.py").write_text(
            "A_RC = 143\nB_RC = 143\n")
        act, _ = contract_run(tmp_path, {"XGT016"})
        assert len(act) == 1 and "twice" in act[0].message

    def test_exit_codes_inventory_section(self, tmp_path):
        self._registry(tmp_path)
        _, eng = contract_run(tmp_path, {"XGT016"})
        assert eng.inventory()["exit_codes"] == {
            "FENCE_RC": 143, "HOST_LOSS_RC": 144}

    def test_real_registry_matches_inventory(self):
        from xgboost_tpu.reliability import rc
        eng = ContractEngine(REPO_ROOT, fact_paths=[PKG_DIR, TOOLS_DIR])
        assert eng.inventory()["exit_codes"] == rc.registry()
        assert len(rc.registry()) >= 6


# ----------------------------------------------------------------- XGT017
EVENT_DOC = ("# obs\n"
             "## Event inventory\n"
             "| event | emitted when |\n"
             "|---|---|\n"
             "| `gang.fence` | self-fence |\n"
             "| `pipeline.{gate,publish}` | lifecycle |\n"
             "## Next section\n"
             "prose mention of `other.event` does not count\n")


class TestEventNameDrift:
    def test_undocumented_event_fires_at_emit_site(self, tmp_path):
        (tmp_path / "OBSERVABILITY.md").write_text(EVENT_DOC)
        (tmp_path / "w.py").write_text(
            "from xgboost_tpu.obs import trace\n"
            "def go():\n"
            "    trace.event('gang.fence', rank=1)\n"
            "    trace.event('pipeline.gate')\n"
            "    trace.event('pipeline.publish')\n"
            "    trace.event('gang.mystery', rank=1)\n")
        act, _ = contract_run(tmp_path, {"XGT017"})
        assert len(act) == 1
        assert "gang.mystery" in act[0].message and act[0].line == 6

    def test_stale_doc_row_fires_at_doc_line(self, tmp_path):
        (tmp_path / "OBSERVABILITY.md").write_text(EVENT_DOC)
        (tmp_path / "w.py").write_text(
            "from xgboost_tpu.obs import trace\n"
            "def go():\n"
            "    trace.event('gang.fence')\n"
            "    trace.event('pipeline.gate')\n"
            "    trace.event('pipeline.publish')\n")
        act, _ = contract_run(tmp_path, {"XGT017"})
        assert act == []  # brace expansion covered both pipeline rows
        (tmp_path / "w.py").write_text(
            "from xgboost_tpu.obs import trace\n"
            "def go():\n    trace.event('pipeline.gate')\n"
            "    trace.event('pipeline.publish')\n")
        act, _ = contract_run(tmp_path, {"XGT017"})
        assert len(act) == 1 and "gang.fence" in act[0].message
        assert act[0].path.endswith("OBSERVABILITY.md")

    def test_heading_scoping_ignores_prose_and_spans(self, tmp_path):
        # `other.event` appears in backticks OUTSIDE the inventory
        # heading: emitting it must still be a finding
        (tmp_path / "OBSERVABILITY.md").write_text(EVENT_DOC)
        (tmp_path / "w.py").write_text(
            "from xgboost_tpu.obs import trace\n"
            "def go():\n"
            "    trace.event('gang.fence')\n"
            "    trace.event('pipeline.gate')\n"
            "    trace.event('pipeline.publish')\n"
            "    trace.event('other.event')\n")
        act, _ = contract_run(tmp_path, {"XGT017"})
        assert len(act) == 1 and "other.event" in act[0].message

    def test_emit_dict_kind_event_counts_span_does_not(self, tmp_path):
        (tmp_path / "OBSERVABILITY.md").write_text(EVENT_DOC)
        (tmp_path / "w.py").write_text(
            "def go(events):\n"
            "    events.emit({'kind': 'event', 'name': 'x.y', 'n': 1})\n"
            "    events.emit({'kind': 'span', 'name': 'span.name'})\n")
        act, eng = contract_run(tmp_path, {"XGT017"})
        emitted = {n for _, n, _ in eng.facts().events}
        assert emitted == {"x.y"}
        msgs = [f.message for f in act]
        assert any("x.y" in m for m in msgs)
        assert not any("span.name" in m for m in msgs)

    def test_real_tree_roundtrips(self):
        from xgboost_tpu.analysis.contracts import _doc_event_table
        eng = ContractEngine(REPO_ROOT, fact_paths=[PKG_DIR, TOOLS_DIR])
        emitted = {n for _, n, _ in eng.facts().events}
        with open(os.path.join(REPO_ROOT, "OBSERVABILITY.md")) as f:
            documented = set(_doc_event_table(f.read()))
        assert emitted, "event extraction found nothing — collector broke"
        assert emitted == documented
        assert set(eng.inventory()["events"]) == emitted


# ------------------------------------------------------- inventory drift
class TestInventoryDrift:
    def _seeded(self, tmp_path):
        (tmp_path / "reliability").mkdir()
        (tmp_path / "reliability" / "rc.py").write_text(RC_SRC)
        (tmp_path / "OBSERVABILITY.md").write_text(EVENT_DOC)
        (tmp_path / "w.py").write_text(
            "from xgboost_tpu.obs import trace\n"
            "def go():\n"
            "    trace.event('gang.fence')\n"
            "    trace.event('pipeline.gate')\n"
            "    trace.event('pipeline.publish')\n")
        eng = ContractEngine(str(tmp_path), codes={"XGT016", "XGT017"})
        eng.write_inventory()
        return eng

    def test_fresh_inventory_is_clean(self, tmp_path):
        self._seeded(tmp_path)
        act, _ = contract_run(tmp_path, {"XGT016", "XGT017"})
        assert act == []

    def test_unregistered_addition_drifts_each_section(self, tmp_path):
        self._seeded(tmp_path)
        path = tmp_path / "ANALYSIS_CONTRACTS.json"
        committed = json.loads(path.read_text())
        committed["exit_codes"]["ROGUE_RC"] = 99
        committed["events"].append("rogue.event")
        path.write_text(json.dumps(committed))
        act, _ = contract_run(tmp_path, {"XGT016", "XGT017"})
        assert sorted(f.rule for f in act) == ["XGT016", "XGT017"]
        assert all("stale" in f.message for f in act)

    def test_drift_findings_scope_to_enabled_codes(self, tmp_path):
        self._seeded(tmp_path)
        path = tmp_path / "ANALYSIS_CONTRACTS.json"
        committed = json.loads(path.read_text())
        committed["events"].append("rogue.event")
        path.write_text(json.dumps(committed))
        act, _ = contract_run(tmp_path, {"XGT016"})
        assert act == []  # the drifted section belongs to XGT017


# ------------------------------------------------------------ enforcement
class TestWholeTreeClean:
    def test_dataflow_rules_clean_over_repo(self):
        rc = lint_main([PKG_DIR, TOOLS_DIR, "--rules",
                        "XGT013,XGT014,XGT015", "--no-baseline"])
        assert rc == 0

    def test_contract_rules_clean_over_repo(self):
        rc = lint_main(["--rules", "XGT016,XGT017", "--no-baseline",
                        PKG_DIR])
        assert rc == 0

    def test_baseline_is_empty(self):
        # the v3 ISSUE's bar: every finding was FIXED (or inline-
        # suppressed with a rationale), none accepted as debt
        path = os.path.join(REPO_ROOT, "ANALYSIS_BASELINE.json")
        with open(path) as f:
            assert json.load(f)["findings"] == {}

    def test_committed_inventory_has_v3_sections(self):
        path = os.path.join(REPO_ROOT, "ANALYSIS_CONTRACTS.json")
        with open(path) as f:
            inv = json.load(f)
        assert inv["exit_codes"] and inv["events"]
        assert inv["exit_codes"]["FENCE_RC"] == 143


# ------------------------------------------------------------------ SARIF
BAD_SARIF_SRC = ("import jax\n"
                 "fn = jax.jit(impl, donate_argnums=(0,))\n"
                 "def run(m):\n"
                 "    out = fn(m, 3)\n"
                 "    return m.sum()\n"
                 "@jax.jit\n"
                 "def step(x):\n"
                 "    print(x)\n"
                 "    return x\n")


class TestSarif:
    def test_sarif_roundtrips_against_json(self, tmp_path, capsys):
        p = tmp_path / "bad.py"
        p.write_text(BAD_SARIF_SRC)
        argv = [str(p), "--no-baseline", "--no-contracts"]
        rc_sarif = lint_main(argv + ["--sarif"])
        sarif = json.loads(capsys.readouterr().out)
        rc_json = lint_main(argv + ["--json"])
        plain = json.loads(capsys.readouterr().out)
        assert rc_sarif == rc_json == 1  # same exit contract
        assert sarif["version"] == "2.1.0"
        assert "sarif-2.1.0" in sarif["$schema"]
        # one run per rule code, each self-describing
        run_rules = [r["tool"]["driver"]["rules"][0]["id"]
                     for r in sarif["runs"]]
        assert run_rules == sorted(run_rules)
        assert set(run_rules) == {"XGT013", "XGT014"}
        flat = {(res["ruleId"],
                 res["locations"][0]["physicalLocation"]["region"]
                    ["startLine"],
                 res["message"]["text"])
                for run in sarif["runs"] for res in run["results"]}
        expect = {(f["rule"], f["line"], f["message"])
                  for f in plain["findings"]}
        assert flat == expect and flat

    def test_clean_tree_emits_catalog_run(self, tmp_path, capsys):
        p = tmp_path / "ok.py"
        p.write_text("X = 1\n")
        rc = lint_main([str(p), "--no-baseline", "--no-contracts",
                        "--sarif"])
        sarif = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert len(sarif["runs"]) == 1
        run = sarif["runs"][0]
        assert run["results"] == []
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        # the full catalog rides along so consumers can tell "ran
        # clean" from "didn't run"
        for code in ("XGT001", "XGT013", "XGT016", "XGT017"):
            assert code in rule_ids

    def test_json_and_sarif_are_mutually_exclusive(self, tmp_path):
        p = tmp_path / "ok.py"
        p.write_text("X = 1\n")
        assert lint_main([str(p), "--json", "--sarif"]) == 2


# ---------------------------------------------------------- DonationGuard
jax = pytest.importorskip("jax")


class TestDonationGuard:
    def test_post_call_touch_raises(self):
        import jax.numpy as jnp

        from xgboost_tpu.analysis.runtime import DonationGuard
        fn = jax.jit(lambda m, k: m + k, donate_argnums=(0,))
        guard = DonationGuard(donate_argnums=(0,))
        wrapped = guard.wrap(fn)
        m = jnp.ones((8,))
        out = wrapped(m, 2.0)
        assert guard.calls == 1
        assert float(out[0]) == 3.0
        with pytest.raises(RuntimeError, match="deleted"):
            m.sum()
        guard.assert_clean()  # the violation is the CALLER's, and raised

    def test_reuse_of_donated_buffer_is_recorded(self):
        import jax.numpy as jnp

        from xgboost_tpu.analysis.runtime import DonationGuard
        fn = jax.jit(lambda m, k: m + k, donate_argnums=(0,))
        guard = DonationGuard(donate_argnums=(0,))
        wrapped = guard.wrap(fn)
        m = jnp.ones((8,))
        wrapped(m, 2.0)
        with pytest.raises(RuntimeError):
            wrapped(m, 2.0)  # jax itself refuses the dead buffer...
        with pytest.raises(AssertionError, match="donated-reuse"):
            guard.assert_clean()  # ...and the guard names the hazard

    def test_non_donatable_position_is_recorded(self):
        from xgboost_tpu.analysis.runtime import DonationGuard
        fn = jax.jit(lambda m, k: m + k, donate_argnums=(0,))
        guard = DonationGuard(donate_argnums=(1,))
        guard.wrap(fn)(jax.numpy.ones((4,)), 2.0)  # pos 1 is a scalar
        with pytest.raises(AssertionError, match="non-donatable"):
            guard.assert_clean()

    def test_empty_pytree_at_donated_position_is_vacuously_fine(self):
        # gbtree donates tuple(eval_margins) unconditionally; a
        # no-evals run passes () there — nothing to donate, no noise
        from xgboost_tpu.analysis.runtime import DonationGuard
        fn = jax.jit(lambda m, ems: m * 2, donate_argnums=(0, 1))
        guard = DonationGuard(donate_argnums=(0, 1))
        guard.wrap(fn)(jax.numpy.ones((4,)), ())
        assert guard.calls == 1
        guard.assert_clean()

    @pytest.mark.filterwarnings("ignore:Some donated buffers")
    def test_real_scan_rounds_dispatch_is_donation_clean(self,
                                                         monkeypatch):
        """The runtime cross-check of XGT013 over the REAL fused path:
        wrap ``_scan_rounds`` (the one wrapping: its carries are
        donated on every backend, deleted on this one as a TPU reuses
        them) and train multi-segment with evals — if
        ``do_boost_fused`` (or anything downstream) read a donated
        margin after dispatch, this run would raise 'Array has been
        deleted'."""
        import xgboost_tpu as xgb
        from xgboost_tpu.analysis.runtime import DonationGuard
        from xgboost_tpu.learner import Booster
        from xgboost_tpu.models import gbtree

        guard = DonationGuard(donate_argnums=(1, 11))
        monkeypatch.setattr(
            gbtree, "_scan_rounds", guard.wrap(gbtree._scan_rounds))

        rng = np.random.RandomState(0)
        X = rng.rand(400, 6).astype(np.float32)
        y = (X[:, 0] > 0.5).astype(np.float32)
        dtrain = xgb.DMatrix(X, label=y)
        Xe = rng.rand(100, 6).astype(np.float32)
        deval = xgb.DMatrix(Xe, label=(Xe[:, 0] > 0.5).astype(np.float32))
        bst = Booster({"objective": "binary:logistic", "max_depth": 3,
                       "eta": 0.3, "eval_metric": "logloss"},
                      cache=[dtrain, deval])
        lines = []
        bst.update_many(dtrain, 0, 6,
                        evals=[(dtrain, "train"), (deval, "eval")],
                        eval_callback=lambda i, msg: lines.append(msg),
                        rounds_per_dispatch=3)
        assert guard.calls >= 2    # 6 rounds / 3 per dispatch
        assert len(lines) == 6     # eval lines came from live buffers
        guard.assert_clean()
        preds = np.asarray(bst.predict(dtrain))  # post-run predict OK
        assert preds.shape == (400,)
