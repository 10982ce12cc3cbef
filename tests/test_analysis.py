"""The repo-native static-analysis suite (docs/analysis.md).

Three layers, all tier-1:

- **Fixture corpus**: per checker, one tree of true positives and one
  of correct code that must stay finding-free (the false-positive
  guard) — ``tests/analysis_fixtures/``.
- **Mutation gates**: deleting the PR 2 series ``.remove()`` calls or
  widening the PR 4 never-donate guard in a copy of the REAL source
  makes the suite fail — the acceptance property that the checkers
  actually protect the invariants they claim to.
- **Integration**: the suite runs clean on this repo against the
  committed baseline (zero new findings), and the baseline itself
  stays short and reason-annotated.
"""

import json
import os
import shutil
import subprocess
import sys

from rafiki_tpu.analysis import core
from rafiki_tpu.analysis.core import (
    Finding,
    load_baseline,
    run_suite,
    save_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "analysis_fixtures")


def _tree(tmp_path, *fixture_files):
    pkg = tmp_path / "rafiki_tpu"
    pkg.mkdir(exist_ok=True)
    for name in fixture_files:
        shutil.copy(os.path.join(FIXTURES, name), pkg / name)
    return str(tmp_path)


def _codes(report):
    return sorted({f.code for f in report.findings})


def _run(root, checker):
    return run_suite(root, only=[checker])


# --- Fixture corpus: true positive + false-positive guard per checker


def test_guarded_state_true_positives(tmp_path):
    report = _run(_tree(tmp_path, "guarded_tp.py"), "guarded-state")
    codes = _codes(report)
    assert "RTA101" in codes and "RTA102" in codes and "RTA103" in codes
    by_anchor = {f.anchor for f in report.findings}
    assert "UnguardedAccess._depth@depth" in by_anchor
    # module-global arm: a global guarded by a module lock at some
    # accesses but read bare in a free function
    assert "guarded_tp:_mod_depth@mod_depth" in by_anchor
    assert "SelfDeadlock:_lock->_lock" in by_anchor
    assert "LockOrderCycle:_a<->_b" in by_anchor
    # the blocking sleep AND the open() under the lock
    assert any("time.sleep" in f.message for f in report.findings)
    assert any("open()" in f.message for f in report.findings)


def test_guarded_state_false_positive_guard(tmp_path):
    report = _run(_tree(tmp_path, "guarded_fp.py"), "guarded-state")
    assert report.findings == [], [f.render() for f in report.findings]


def test_thread_lifecycle_true_positives(tmp_path):
    report = _run(_tree(tmp_path, "thread_tp.py"), "thread-lifecycle")
    codes = _codes(report)
    assert codes == ["RTA201", "RTA202"]


def test_thread_lifecycle_false_positive_guard(tmp_path):
    report = _run(_tree(tmp_path, "thread_fp.py"), "thread-lifecycle")
    assert report.findings == [], [f.render() for f in report.findings]


def test_series_lifecycle_true_positive(tmp_path):
    report = _run(_tree(tmp_path, "series_tp.py"), "series-lifecycle")
    assert _codes(report) == ["RTA301"]
    anchors = {f.anchor for f in report.findings}
    assert "label:service" in anchors
    # r17 attribution-ledger shape: a hashed tenant key and a bin id
    # are dynamic labels exactly like a service id.
    assert "label:tenant" in anchors
    assert "label:bin" in anchors


def test_series_lifecycle_false_positive_guard(tmp_path):
    report = _run(_tree(tmp_path, "series_fp.py"), "series-lifecycle")
    assert report.findings == [], [f.render() for f in report.findings]


def test_donation_true_positives(tmp_path):
    report = _run(_tree(tmp_path, "donation_tp.py"), "donation")
    codes = _codes(report)
    assert "RTA401" in codes and "RTA402" in codes
    # the cache-tainted array reached the donated slot via the
    # dispatch forwarder, not a direct call
    assert any("data_dev" in f.message for f in report.findings
               if f.code == "RTA401")
    # r13: taint flows through neutral-named helper RETURNS (and a
    # helper-calls-helper chain) into the donated slot
    assert any("resident" in f.message for f in report.findings
               if f.code == "RTA401")


def test_donation_false_positive_guard(tmp_path):
    report = _run(_tree(tmp_path, "donation_fp.py"), "donation")
    assert report.findings == [], [f.render() for f in report.findings]


def test_drift_true_positives(tmp_path):
    root = str(tmp_path / "t")
    shutil.copytree(os.path.join(FIXTURES, "drift_tp"), root)
    report = _run(root, "drift")
    codes = _codes(report)
    assert codes == ["RTA501", "RTA502", "RTA503", "RTA504", "RTA505",
                     "RTA506"]
    msgs = "\n".join(f.message for f in report.findings)
    assert "rafiki_tpu_serving_widgets" in msgs          # shape
    assert "'mystery'" in msgs                           # subsystem
    assert "rafiki_tpu_bus_retries_seconds" in msgs      # counter unit
    assert "rafiki_tpu_renamed_away_total" in msgs       # dashboard
    assert "RAFIKI_TPU_MYSTERY_KNOB" in msgs             # docs + parity
    assert "RAFIKI_TPU_ROGUE_TWEAK" in msgs              # rogue env
    # RTA506 fires on BOTH sources: the consumed-series vocabulary in
    # observe/slo.py and a docs/slo rules file's metric override.
    assert "rafiki_tpu_serving_gone_seconds" in msgs     # slo module
    assert "rafiki_tpu_serving_vanished_seconds" in msgs  # rules file
    # ...but a rule naming a registered series stays clean.
    assert "rafiki_tpu_bus_wait_seconds'" not in msgs


def test_drift_false_positive_guard(tmp_path):
    root = str(tmp_path / "t")
    shutil.copytree(os.path.join(FIXTURES, "drift_fp"), root)
    report = _run(root, "drift")
    assert report.findings == [], [f.render() for f in report.findings]


def test_concurrency_true_positives(tmp_path):
    root = str(tmp_path / "t")
    shutil.copytree(os.path.join(FIXTURES, "concurrency_tp"), root)
    report = _run(root, "concurrency")
    codes = _codes(report)
    assert codes == ["RTA104", "RTA105", "RTA106"]
    by_anchor = {f.anchor: f for f in report.findings}
    # The cross-class cycle was found through a >=3-frame cross-module
    # chain: the message must name the intermediate frames.
    cyc = by_anchor["Coordinator._lock<->StatsSink._lock"]
    assert "Coordinator._tick" in cyc.message
    assert "Coordinator._note" in cyc.message
    assert "sink.py" in cyc.message  # the reverse path's module
    # Blocking two module-function frames down, none of it in admit().
    blk = by_anchor["Admission.admit->_backoff:time.sleep()"]
    assert "_backoff -> _pause" in blk.message
    # Thread-root pair sharing an attribute: Thread target and an HTTP
    # route handler both fire.
    assert "Poller._latest:cross-root" in by_anchor
    assert "MiniService._hits:cross-root" in by_anchor
    # Cross-class root: the owner registers Thread(target=
    # self.consumer.loop); the finding lands on the CONSUMER's class.
    cc = by_anchor["BusConsumer._seen:cross-root"]
    assert "'loop'" in cc.message and cc.path.endswith("consumer.py")
    # Executor form of the same blindness: the owner's
    # pool.submit(self.stage.drain) makes drain a root on the
    # consumer's class too.
    sc = by_anchor["SubmitConsumer._polled:cross-root"]
    assert "'drain'" in sc.message and sc.path.endswith("consumer.py")
    # Module-global lock, chained blocking (free functions only the
    # whole-program pass can see)...
    mg = by_anchor["publish->_settle:time.sleep()"]
    assert "rafiki_tpu.registry._REG_LOCK" in mg.message
    # ...the direct form RTA102 can never reach...
    assert "drain:time.sleep():direct" in by_anchor
    # ...and a lock-order cycle between a CLASS lock and a MODULE one.
    assert "Journal._lock<->rafiki_tpu.registry._REG_LOCK" in by_anchor
    # r19 carry: the DOTTED spelling (``registry._REG_LOCK`` from a
    # ``from rafiki_tpu import registry`` import) must unify with the
    # bare name — a free function blocking under it...
    dd = by_anchor["flush:time.sleep():direct"]
    assert "rafiki_tpu.registry._REG_LOCK" in dd.message
    assert dd.path.endswith("dotted.py")
    # ...and a class-vs-module cycle reached only through the dotted
    # reference.
    assert "Ledger._lock<->rafiki_tpu.registry._REG_LOCK" in by_anchor
    # socketserver shape: ``FrameServer((h, p), FrameHandler)`` makes
    # handle() a per-connection thread root on the HANDLER class.
    hh = by_anchor["FrameHandler._hits:cross-root"]
    assert "'handle'" in hh.message and hh.path.endswith("server.py")
    # Spawn-PARAMETER root: the owner hands self.worker.loop to a
    # DIFFERENT class's register_consumer(fn) — which is what calls
    # Thread(target=fn) — and the root still lands on the worker.
    sp = by_anchor["ParamWorker._seen:cross-root"]
    assert "'loop'" in sp.message and sp.path.endswith("spawnhelper.py")
    # Module<->module lock-order cycle: two free functions, no class
    # anywhere — only the module-owner cycle arm sees both directions.
    assert ("rafiki_tpu.modlocks._FLUSH_LOCK<->"
            "rafiki_tpu.modlocks._INGEST_LOCK") in by_anchor


def test_concurrency_false_positive_guard(tmp_path):
    root = str(tmp_path / "t")
    shutil.copytree(os.path.join(FIXTURES, "concurrency_fp"), root)
    report = _run(root, "concurrency")
    assert report.findings == [], [f.render() for f in report.findings]


def test_import_hygiene_true_positives(tmp_path):
    root = str(tmp_path / "t")
    shutil.copytree(os.path.join(FIXTURES, "imports_tp"), root)
    report = _run(root, "import-hygiene")
    codes = _codes(report)
    assert codes == ["RTA601", "RTA602"]
    msgs = "\n".join(f.message for f in report.findings)
    assert "builds/starts a thread" in msgs
    assert "binds a socket/server" in msgs
    assert "spawns a process" in msgs
    assert "APP_DEBUG" in msgs       # module-level env read
    assert "APP_LEASE" in msgs       # class-BODY env read (executes
    #                                  on import — the NODE_LEASE bug)
    assert "APP_ELSE" in msgs        # else-arm of a __main__ guard
    assert "APP_INVERTED" in msgs    # body of an inverted guard
    assert "APP_SUB_LEASE" in msgs   # os.environ["X"] subscript read
    jax_f = [f for f in report.findings if f.code == "RTA602"]
    assert len(jax_f) == 1
    # The finding names the import chain from the bus root.
    assert "rafiki_tpu/bus/broker.py -> rafiki_tpu/heavy.py" \
        in jax_f[0].message


def test_import_hygiene_false_positive_guard(tmp_path):
    root = str(tmp_path / "t")
    shutil.copytree(os.path.join(FIXTURES, "imports_fp"), root)
    report = _run(root, "import-hygiene")
    assert report.findings == [], [f.render() for f in report.findings]


def test_flow_true_positives(tmp_path):
    root = str(tmp_path / "t")
    shutil.copytree(os.path.join(FIXTURES, "flow_tp"), root)
    report = _run(root, "flow")
    assert _codes(report) == ["RTA701", "RTA702", "RTA703"]
    by_anchor = {f.anchor: f for f in report.findings}
    # RTA701: a family pushed but never popped, a family popped but
    # never pushed, and a control-frame op token on each unbalanced
    # side (produced-never-dispatched / dispatched-never-produced).
    assert "queue:work:" in by_anchor
    assert "queue:lost:" in by_anchor
    assert "op-token:__flush__" in by_anchor
    assert "op-token:__drain2__" in by_anchor
    # RTA702: a client typo that matches no served route, and a served
    # route no in-tree caller reaches.
    typo = by_anchor["route-call:GET /thingz"]
    assert typo.path.endswith("client.py")
    assert "route:POST /orphan" in by_anchor
    # RTA703: every off-path leak class for the fabric flag — an
    # import-time thread in the owned module, owned-module effects in
    # unprotected functions, an owned-prefix series registered outside
    # the owned module, and an ungated constructor of an owned class.
    flag = "RAFIKI_TPU_CLUSTER_FABRIC"
    assert f"{flag}:import-effect:Thread()" in by_anchor
    assert (f"{flag}:offpath:NodeRegistry.__init__:"
            "rafiki_tpu_node_peers") in by_anchor
    assert f"{flag}:offpath:spawn_pinger:Thread()" in by_anchor
    assert f"{flag}:series:rafiki_tpu_serving_fabric_total" in by_anchor
    assert (f"{flag}:unguarded-ctor:NodeRegistry@"
            "Platform.__init__") in by_anchor


def test_flow_false_positive_guard(tmp_path):
    root = str(tmp_path / "t")
    shutil.copytree(os.path.join(FIXTURES, "flow_fp"), root)
    report = _run(root, "flow")
    assert report.findings == [], [f.render() for f in report.findings]


# --- Waivers -----------------------------------------------------------


def test_waiver_with_reason_suppresses(tmp_path):
    pkg = tmp_path / "rafiki_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"
        "    def b(self):\n"
        "        # rta: disable=RTA101 benign monotonic peek\n"
        "        return self._n\n")
    report = run_suite(str(tmp_path), only=["guarded-state"])
    assert report.new == []
    waived = [f for f in report.findings if f.status == "waived"]
    assert len(waived) == 1
    assert waived[0].reason == "benign monotonic peek"


def test_waiver_without_reason_is_its_own_finding(tmp_path):
    pkg = tmp_path / "rafiki_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"
        "    def b(self):\n"
        "        # rta: disable=RTA101\n"
        "        return self._n\n")
    report = run_suite(str(tmp_path), only=["guarded-state"])
    new_codes = sorted(f.code for f in report.new)
    # the reasonless waiver does NOT suppress, and is flagged itself
    assert new_codes == ["RTA001", "RTA101"]


def test_waiver_class_form_covers_all_codes(tmp_path):
    pkg = tmp_path / "rafiki_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import threading, time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"
        "            # rta: disable=RTA1xx startup-only path, held <1ms\n"
        "            time.sleep(0.001)\n")
    report = run_suite(str(tmp_path), only=["guarded-state"])
    assert report.new == []
    assert any(f.status == "waived" and f.code == "RTA102"
               for f in report.findings)


def test_stale_waiver_true_positive(tmp_path):
    """A reasoned waiver whose finding no longer fires is RTA003 —
    and the unknown-code form is covered by a FULL run."""
    root = _tree(tmp_path, "stale_waiver_tp.py")
    report = run_suite(root, only=["guarded-state"])
    stale = [f for f in report.new if f.code == "RTA003"]
    # Only the RTA101 waiver under --checker scoping (RTA999 belongs
    # to no ran checker, so the scoped run cannot judge it).
    assert len(stale) == 1 and "RTA101" in stale[0].message
    full = run_suite(root)
    msgs = [f.message for f in full.new if f.code == "RTA003"]
    assert len(msgs) == 2 and any("RTA999" in m for m in msgs)


def test_stale_waiver_false_positive_guard(tmp_path):
    """A waiver that suppresses a live finding (same-line and
    comment-above forms) is never stale."""
    report = run_suite(_tree(tmp_path, "stale_waiver_fp.py"),
                       only=["guarded-state"])
    assert not [f for f in report.new if f.code == "RTA003"]
    assert len([f for f in report.findings
                if f.status == "waived"]) == 2


def test_stale_waiver_is_unwaivable(tmp_path):
    pkg = tmp_path / "rafiki_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "def f():\n"
        "    # rta: disable=RTA003 trying to silence the detector\n"
        "    # rta: disable=RTA101 stale reason\n"
        "    return 1\n")
    report = run_suite(str(tmp_path), only=["guarded-state"])
    codes = sorted(f.code for f in report.new)
    assert codes.count("RTA003") >= 1  # the stale RTA101 waiver
    # ... and the RTA003-waiver itself is both inert and stale.
    assert codes.count("RTA003") == 2


def test_stale_waiver_skipped_in_changed_mode(tmp_path):
    """--changed runs see a partial file view; stale-waiver judgment
    would be unsound there and must not fire."""
    root = _tree(tmp_path, "stale_waiver_tp.py")
    report = run_suite(root, changed={"rafiki_tpu/stale_waiver_tp.py"})
    assert not [f for f in report.findings if f.code == "RTA003"]


def test_fixing_waived_finding_without_deleting_waiver_fails_suite(
        tmp_path):
    """Mutation gate on REAL source: jax_model.py's RTA301 waiver is
    live because the train loop samples per-trial labels; removing
    the labeled samples while keeping the comment must turn the suite
    red with RTA003 (the rotting-disable class)."""
    clean = _mutated_tree(tmp_path / "clean",
                          "rafiki_tpu/model/jax_model.py", [])
    report = run_suite(clean, only=["series-lifecycle"])
    assert not [f for f in report.new
                if f.code in ("RTA003", "RTA301")]
    mutated = _mutated_tree(
        tmp_path / "mut", "rafiki_tpu/model/jax_model.py",
        [(", **_mlabels)", ")")])
    report = run_suite(mutated, only=["series-lifecycle"])
    assert any(f.code == "RTA003" for f in report.new)


def test_waiver_inside_string_literal_is_inert(tmp_path):
    """Waiver-shaped text in a string/docstring is not a comment: it
    must neither suppress the adjacent finding nor mint an RTA001."""
    pkg = tmp_path / "rafiki_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"
        "    def b(self):\n"
        '        s = "# rta: disable=RTA101 just a string"\n'
        "        return self._n, s\n")
    report = run_suite(str(tmp_path), only=["guarded-state"])
    new_codes = sorted(f.code for f in report.new)
    assert new_codes == ["RTA101"]  # not waived, and no RTA001


def test_thread_in_module_level_block_is_flagged(tmp_path):
    """A non-daemon, never-joined Thread built under a module-level
    if/try block is still module-level — the checker must descend."""
    pkg = tmp_path / "rafiki_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import threading\n"
        "if True:\n"
        "    t = threading.Thread(target=print)\n"
        "    t.start()\n")
    report = run_suite(str(tmp_path), only=["thread-lifecycle"])
    assert any(f.code == "RTA201" for f in report.new), \
        [f.render() for f in report.findings]


# --- Baseline ----------------------------------------------------------


def test_baseline_freezes_and_unreviewed_fails(tmp_path):
    pkg = tmp_path / "rafiki_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"
        "    def b(self):\n"
        "        return self._n\n")
    # A full run needs a loadable NodeConfig (RTA503) even in a bare
    # fixture tree.
    (pkg / "config.py").write_text(
        "import dataclasses\n\n\n"
        "@dataclasses.dataclass\n"
        "class NodeConfig:\n"
        "    pass\n")
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "ops.md").write_text("# Ops\n")
    ident = "RTA101:rafiki_tpu/mod.py:C._n@b"
    # A reviewed reason freezes the finding.
    report = run_suite(str(tmp_path), only=["guarded-state"],
                       baseline={ident: "pre-existing, tracked in r10"})
    assert report.new == []
    assert any(f.status == "baselined" for f in report.findings)
    # An UNREVIEWED placeholder keeps failing via RTA002.
    report = run_suite(str(tmp_path), only=["guarded-state"],
                       baseline={ident: "UNREVIEWED: fill me in"})
    assert any(f.code == "RTA002" for f in report.new)
    # A stale entry is reported for pruning, not a failure — but only
    # on a FULL run: a scoped run never produces findings for
    # unscanned checkers/files, so its "missing" entries aren't fixed.
    stale_bl = {ident: "ok reason",
                "RTA101:rafiki_tpu/gone.py:X._y@z": "fixed long ago"}
    report = run_suite(str(tmp_path), baseline=stale_bl)
    assert report.new == []
    assert report.stale_baseline == ["RTA101:rafiki_tpu/gone.py:X._y@z"]
    report = run_suite(str(tmp_path), only=["guarded-state"],
                       baseline=stale_bl)
    assert report.new == []
    assert report.stale_baseline == []


def test_update_baseline_round_trip(tmp_path):
    findings = [Finding(code="RTA101", path="rafiki_tpu/m.py", line=3,
                        message="msg", anchor="C._n@b")]
    path = str(tmp_path / "baseline.json")
    save_baseline(path, findings, prior={})
    loaded = load_baseline(path)
    ident = "RTA101:rafiki_tpu/m.py:C._n@b"
    assert ident in loaded and loaded[ident].startswith("UNREVIEWED")
    # a human writes the reason; re-saving preserves it
    save_baseline(path, findings,
                  prior={ident: "benign: snapshot read"})
    assert load_baseline(path)[ident] == "benign: snapshot read"
    # meta-findings are never frozen: the classifier ignores baseline
    # entries for them, so saving them would only accrete dead weight
    save_baseline(path, findings + [
        Finding(code="RTA001", path="rafiki_tpu/m.py", line=9,
                message="waiver without a reason", anchor="waiver:9")],
        prior={ident: "benign: snapshot read"})
    assert list(load_baseline(path)) == [ident]


def test_update_baseline_refuses_changed_scope(tmp_path):
    """--changed --update-baseline would rewrite the baseline from a
    partial report, silently dropping every frozen entry outside the
    changed set — the CLI must refuse the combination."""
    proc = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.analysis", "--changed",
         "--update-baseline",
         "--baseline", str(tmp_path / "bl.json")],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 2
    assert "requires a full run" in proc.stderr
    assert not (tmp_path / "bl.json").exists()


# --- Mutation gates: the suite protects the real invariants -----------


def _mutated_tree(tmp_path, rel_src, replacements, dst_name=None):
    """Copy ONE real source file into a fixture tree, applying textual
    mutations. ``dst_name`` may carry subdirectories (to preserve a
    package path the checker keys on, e.g. ``bus/base.py``)."""
    with open(os.path.join(REPO, rel_src), encoding="utf-8") as f:
        text = f.read()
    for old, new in replacements:
        assert old in text, f"mutation target {old!r} missing in {rel_src}"
        text = text.replace(old, new)
    dst = tmp_path / "rafiki_tpu" / (dst_name or
                                     os.path.basename(rel_src))
    dst.parent.mkdir(parents=True, exist_ok=True)
    dst.write_text(text)
    return str(tmp_path)


def test_deleting_serving_stats_remove_fails_suite(tmp_path):
    """PR 2 invariant: ServingStats.close() must drop its per-instance
    series; deleting the .remove() call is a suite failure."""
    clean = _mutated_tree(tmp_path / "clean",
                          "rafiki_tpu/observe/serving.py", [])
    report = run_suite(clean, only=["series-lifecycle"])
    assert not [f for f in report.new if f.code == "RTA301"]
    mutated = _mutated_tree(tmp_path / "mut",
                            "rafiki_tpu/observe/serving.py",
                            [("m.remove(service=self.service)", "pass")])
    report = run_suite(mutated, only=["series-lifecycle"])
    assert any(f.code == "RTA301" and f.anchor == "label:service"
               for f in report.new)


def test_deleting_trial_series_remove_fails_suite(tmp_path):
    """PR 2 invariant: TrialRunner must drop the per-trial train
    series at trial end; deleting the .remove() call is a failure."""
    clean = _mutated_tree(tmp_path / "clean",
                          "rafiki_tpu/worker/runner.py", [])
    report = run_suite(clean, only=["series-lifecycle"])
    assert not [f for f in report.new if f.code == "RTA301"]
    mutated = _mutated_tree(tmp_path / "mut",
                            "rafiki_tpu/worker/runner.py",
                            [("m.remove(trial=trial_id[:12])", "pass")])
    report = run_suite(mutated, only=["series-lifecycle"])
    assert any(f.code == "RTA301" and f.anchor == "label:trial"
               for f in report.new)


def test_donating_staged_arrays_fails_suite(tmp_path):
    """PR 4 invariant: the staged dataset arrays are never donated;
    widening donate_argnums to cover them is a suite failure."""
    clean = _mutated_tree(tmp_path / "clean",
                          "rafiki_tpu/model/jax_model.py", [])
    report = run_suite(clean, only=["donation"])
    assert not [f for f in report.new if f.code.startswith("RTA4")]
    mutated = _mutated_tree(tmp_path / "mut",
                            "rafiki_tpu/model/jax_model.py",
                            [("donate_argnums=(0,)",
                              "donate_argnums=(0, 1, 2)")])
    report = run_suite(mutated, only=["donation"])
    assert any(f.code == "RTA401" for f in report.new), \
        [f.render() for f in report.new]


def test_unguarded_cross_thread_write_fails_suite(tmp_path):
    """r14 breaker-class invariant: _PersistStage state is shared
    between the executor-submitted tail and the trial loop ONLY under
    its lock; stripping the locks (the unguarded-cross-thread-write
    mutation) must turn the suite red via RTA106."""
    clean = _mutated_tree(tmp_path / "clean",
                          "rafiki_tpu/worker/runner.py", [])
    report = run_suite(clean, only=["concurrency"])
    assert not [f for f in report.new if f.code == "RTA106"], \
        [f.render() for f in report.new]
    mutated = _mutated_tree(tmp_path / "mut",
                            "rafiki_tpu/worker/runner.py",
                            [("with self._lock:", "if True:")])
    report = run_suite(mutated, only=["concurrency"])
    cross = [f for f in report.new if f.code == "RTA106"]
    assert any(f.anchor == "_PersistStage._pending:cross-root"
               for f in cross), [f.render() for f in report.new]


def test_unguarded_decode_admission_queue_fails_suite(tmp_path):
    """r18 invariant: DecodeScheduler._pending is the ONE piece of
    state shared between the serve-loop thread (submit) and the decode
    loop — a thread the scheduler never constructs itself
    (InferenceWorker registers Thread(target=self._gen_sched.loop)),
    so only the cross-class root inventory can see the pair. Stripping
    the Condition must turn the suite red via RTA106."""
    for name, reps in (
            ("clean", []),
            ("mut", [("with self._cv:", "if True:")])):
        root = _mutated_tree(tmp_path / name,
                             "rafiki_tpu/worker/decode_scheduler.py",
                             reps, dst_name="worker/decode_scheduler.py")
        _mutated_tree(tmp_path / name, "rafiki_tpu/worker/inference.py",
                      [], dst_name="worker/inference.py")
        report = run_suite(root, only=["concurrency"])
        cross = [f for f in report.new if f.code == "RTA106" and
                 f.anchor == "DecodeScheduler._pending:cross-root"]
        if name == "clean":
            assert cross == [], [f.render() for f in cross]
        else:
            assert cross, [f.render() for f in report.new]
            assert "'loop'" in cross[0].message


def test_blocking_under_module_lock_fails_suite(tmp_path):
    """r17 carry: the workload recorder's module-global gate lock sits
    on the request hot path; introducing a sleep under it must turn
    the suite red via RTA105. Free functions are invisible to the
    per-class RTA102 — this gate proves the module-lock plane actually
    protects the real source."""
    clean = _mutated_tree(tmp_path / "clean",
                          "rafiki_tpu/observe/workload.py", [])
    report = run_suite(clean, only=["concurrency"])
    assert not [f for f in report.new if f.code == "RTA105"], \
        [f.render() for f in report.new]
    mutated = _mutated_tree(
        tmp_path / "mut", "rafiki_tpu/observe/workload.py",
        [("    with _lock:\n"
          "        rec = _state[0] if _state is not None else None\n"
          "        _log_dir = log_dir or None",
          "    with _lock:\n"
          "        time.sleep(0.01)\n"
          "        rec = _state[0] if _state is not None else None\n"
          "        _log_dir = log_dir or None")])
    report = run_suite(mutated, only=["concurrency"])
    assert any(f.code == "RTA105" and
               f.anchor == "configure:time.sleep():direct"
               for f in report.new), [f.render() for f in report.new]


def test_handler_thread_root_fails_suite(tmp_path):
    """r19 carry: the TCP broker's ``_Handler`` runs ``handle()`` on a
    per-connection thread because ``_Server((host, port), _Handler)``
    registers it — a root no ``threading.Thread`` scan can see.
    Introducing an unguarded cross-root attribute on the handler must
    turn the suite red via RTA106; the clean source must stay green."""
    clean = _mutated_tree(tmp_path / "clean", "rafiki_tpu/bus/tcp.py", [])
    report = run_suite(clean, only=["concurrency"])
    assert not [f for f in report.new
                if f.code == "RTA106" and "_Handler" in f.anchor], \
        [f.render() for f in report.new]
    mutated = _mutated_tree(
        tmp_path / "mut", "rafiki_tpu/bus/tcp.py",
        [("class _Handler(socketserver.BaseRequestHandler):\n"
          "    def handle(self):",
          "class _Handler(socketserver.BaseRequestHandler):\n"
          "    def frames_served(self):\n"
          "        return self._frames\n"
          "\n"
          "    def handle(self):\n"
          "        self._frames = getattr(self, \"_frames\", 0) + 1")])
    report = run_suite(mutated, only=["concurrency"])
    assert any(f.code == "RTA106" and
               f.anchor == "_Handler._frames:cross-root"
               for f in report.new), [f.render() for f in report.new]


def test_cross_class_lock_inversion_fails_suite(tmp_path):
    """RTA104 gate: the batcher already takes MicroBatcher._cond ->
    ServingStats._lock (stats calls under the admission lock).
    Re-introducing the reverse order — a method that freezes the stats
    lock and then reaches for the admission lock, the accretion shape
    r12-era review had to catch by hand — must fail the suite."""
    inversion = (
        "    def freeze_stats(self):\n"
        "        with self.stats._lock:\n"
        "            with self._cond:\n"
        "                return len(self._queue)\n"
        "\n"
        "    def _retry_after(self) -> float:")
    for name, reps in (("clean", []),
                       ("mut", [("    def _retry_after(self) -> float:",
                                 inversion)])):
        root = _mutated_tree(tmp_path / name,
                             "rafiki_tpu/predictor/batcher.py", reps)
        _mutated_tree(tmp_path / name,
                      "rafiki_tpu/observe/serving.py", [])
        report = run_suite(root, only=["concurrency"])
        cycles = [f for f in report.new if f.code == "RTA104"]
        if name == "clean":
            assert cycles == [], [f.render() for f in cycles]
        else:
            assert any(f.anchor ==
                       "MicroBatcher._cond<->ServingStats._lock"
                       for f in cycles), \
                [f.render() for f in report.new]


def test_eager_jax_on_bus_path_fails_suite(tmp_path):
    """PR 2 lazy-import invariant, now enforced: observe.metrics is
    import-time reachable from the bus package, so adding an eager
    `import jax` there must fail the suite via RTA602."""
    for name, reps in (("clean", []),
                       ("mut", [("import json",
                                 "import jax\nimport json")])):
        root = _mutated_tree(tmp_path / name,
                             "rafiki_tpu/observe/metrics.py", reps,
                             dst_name="observe/metrics.py")
        _mutated_tree(tmp_path / name, "rafiki_tpu/bus/base.py", [],
                      dst_name="bus/base.py")
        report = run_suite(root, only=["import-hygiene"])
        eager = [f for f in report.new if f.code == "RTA602"]
        if name == "clean":
            assert eager == [], [f.render() for f in eager]
        else:
            assert any(f.path == "rafiki_tpu/observe/metrics.py"
                       for f in eager), \
                [f.render() for f in report.new]


def test_renamed_queue_prefix_fails_suite(tmp_path):
    """RTA701 gate: renaming the cache's per-worker push prefix while
    the pop side keeps the old name leaves an orphan producer — the
    exact stringly-typed drift the serving split makes possible."""
    for name, reps in (("clean", []),
                       ("mut", [('push(f"q:{worker_id}"',
                                 'push(f"qx:{worker_id}"')])):
        root = _mutated_tree(tmp_path / name, "rafiki_tpu/cache.py",
                             reps)
        _mutated_tree(tmp_path / name, "rafiki_tpu/bus/base.py", [],
                      dst_name="bus/base.py")
        _mutated_tree(tmp_path / name, "rafiki_tpu/bus/__init__.py",
                      [], dst_name="bus/__init__.py")
        report = run_suite(root, only=["flow"])
        orphan = [f for f in report.new if f.code == "RTA701"]
        if name == "clean":
            assert orphan == [], [f.render() for f in orphan]
        else:
            assert any(f.anchor == "queue:qx:" for f in orphan), \
                [f.render() for f in report.new]


def test_typod_client_route_fails_suite(tmp_path):
    """RTA702 gate: a typo'd path in the client SDK matches no served
    route tuple, and the real route simultaneously goes caller-less."""
    for name, reps in (("clean", []),
                       ("mut", [('("POST", "/models"',
                                 '("POST", "/modelz"')])):
        root = _mutated_tree(tmp_path / name,
                             "rafiki_tpu/client/client.py", reps,
                             dst_name="client/client.py")
        _mutated_tree(tmp_path / name, "rafiki_tpu/admin/app.py", [],
                      dst_name="admin/app.py")
        report = run_suite(root, only=["flow"])
        anchors = {f.anchor for f in report.new}
        if name == "clean":
            assert "route-call:POST /models" not in anchors, anchors
            assert "route:POST /models" not in anchors, anchors
        else:
            assert "route-call:POST /modelz" in anchors, anchors
            assert "route:POST /models" in anchors, anchors


def test_unguarding_fabric_registry_fails_suite(tmp_path):
    """RTA703 gate: widening the cluster-fabric construction gate to
    ``if True:`` makes the node registry — its heartbeat thread and
    its rafiki_tpu_node_peers gauge — reachable with the flag off."""
    gate = 'if _pb(os.environ.get("RAFIKI_TPU_CLUSTER_FABRIC", "0")):'
    for name, reps in (("clean", []), ("mut", [(gate, "if True:")])):
        root = _mutated_tree(tmp_path / name,
                             "rafiki_tpu/platform.py", reps)
        _mutated_tree(tmp_path / name, "rafiki_tpu/admin/nodes.py",
                      [], dst_name="admin/nodes.py")
        report = run_suite(root, only=["flow"])
        offpath = [f for f in report.new if f.code == "RTA703"]
        if name == "clean":
            assert offpath == [], [f.render() for f in offpath]
        else:
            assert any("unguarded-ctor:NodeRegistry" in f.anchor
                       for f in offpath), \
                [f.render() for f in report.new]


# --- CLI: --explain ----------------------------------------------------


def test_cli_explain():
    proc = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.analysis", "--explain",
         "RTA104"], capture_output=True, text=True, timeout=120,
        cwd=REPO)
    assert proc.returncode == 0
    assert "cross-class lock-order cycle" in proc.stdout
    assert "fix   :" in proc.stdout
    proc = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.analysis", "--explain",
         "RTA999"], capture_output=True, text=True, timeout=120,
        cwd=REPO)
    assert proc.returncode == 2
    assert "unknown code" in proc.stderr


def test_catalog_covers_every_registered_code():
    from rafiki_tpu.analysis.catalog import CATALOG

    codes = {c for ch in core.all_checkers() for c in ch.codes}
    codes |= {"RTA000", "RTA001", "RTA002"}
    assert codes <= set(CATALOG), sorted(codes - set(CATALOG))


# --- Integration: this repo, the committed baseline -------------------


def test_repo_is_clean_against_committed_baseline():
    baseline = load_baseline(core.baseline_path())
    report = run_suite(REPO, baseline=baseline)
    assert report.new == [], "\n".join(f.render() for f in report.new)


def test_committed_baseline_is_short_and_reasoned():
    baseline = load_baseline(core.baseline_path())
    assert 0 < len(baseline) <= 25
    for ident, reason in baseline.items():
        assert reason and not reason.startswith("UNREVIEWED"), ident
        assert len(reason) > 15, f"{ident}: reason too thin"


def test_cli_json_exit_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.analysis", "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert data["new"] == 0
    assert data["files"] > 50
    assert all(k.startswith("RTA") for k in data["counts_per_code"])


def test_changed_mode_scopes_per_file_checkers(tmp_path):
    pkg = tmp_path / "rafiki_tpu"
    pkg.mkdir()
    bad = ("import threading\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._n = 0\n"
           "    def a(self):\n"
           "        with self._lock:\n"
           "            self._n += 1\n"
           "    def b(self):\n"
           "        return self._n\n")
    (pkg / "one.py").write_text(bad)
    (pkg / "two.py").write_text(bad)
    full = run_suite(str(tmp_path), only=["guarded-state"])
    assert len(full.new) == 2
    scoped = run_suite(str(tmp_path), changed={"rafiki_tpu/one.py"},
                       only=["guarded-state"])
    assert [f.path for f in scoped.new] == ["rafiki_tpu/one.py"]
    # nothing changed -> nothing to analyze, repo checkers skipped too
    empty = run_suite(str(tmp_path), changed=set())
    assert empty.findings == []


def test_flow_codes_clean_on_real_tree():
    """RTA701–703 acceptance: the distributed-surface checkers run
    green on this repo; inline waivers carry the reviewed exceptions
    (browser/curl-only routes)."""
    report = run_suite(REPO, only=["flow"])
    assert report.new == [], "\n".join(f.render() for f in report.new)
    assert "flow" in report.timings
    waived = {f.code for f in report.findings if f.status == "waived"}
    assert "RTA702" in waived


def test_diff_mode_cli_and_timings(tmp_path):
    """--diff <base> scopes like --changed but against an explicit
    git base, and reports per-checker wall time."""
    proc = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.analysis", "--diff",
         "HEAD"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "timings:" in proc.stderr
    # the wall times also land in the JSON report
    proc = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.analysis", "--json",
         "--checker", "donation"],
        capture_output=True, text=True, timeout=240, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    data = json.loads(proc.stdout)
    assert "donation" in data["timings_s"]
    # --changed and --diff are mutually exclusive scoping modes
    proc = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.analysis", "--changed",
         "--diff", "HEAD"],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert proc.returncode == 2
    # --update-baseline refuses the partial view exactly like
    # --changed
    proc = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.analysis", "--diff",
         "HEAD", "--update-baseline",
         "--baseline", str(tmp_path / "bl.json")],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 2
    assert "requires a full run" in proc.stderr
    assert not (tmp_path / "bl.json").exists()


def test_renaming_slo_consumed_series_fails_suite(tmp_path):
    """RTA506 gate (ISSUE r19): the SLO plane's consumed-series
    vocabulary and the committed docs/slo rules must reference
    registered names; renaming either side turns the suite red."""

    def tree(name, slo_reps, rules_reps):
        root = tmp_path / name
        for rel in ("rafiki_tpu/observe/slo.py",
                    "rafiki_tpu/admin/slo_engine.py",
                    "rafiki_tpu/observe/attribution.py",
                    "rafiki_tpu/observe/serving.py",
                    "rafiki_tpu/utils/service.py"):
            with open(os.path.join(REPO, rel), encoding="utf-8") as f:
                text = f.read()
            if rel.endswith("observe/slo.py"):
                for old, new in slo_reps:
                    assert old in text
                    text = text.replace(old, new)
            dst = root / rel
            dst.parent.mkdir(parents=True, exist_ok=True)
            dst.write_text(text)
        with open(os.path.join(REPO, "docs/slo/serving.json"),
                  encoding="utf-8") as f:
            rules = f.read()
        for old, new in rules_reps:
            assert old in rules
            rules = rules.replace(old, new)
        dst = root / "docs" / "slo" / "serving.json"
        dst.parent.mkdir(parents=True, exist_ok=True)
        dst.write_text(rules)
        return str(root)

    def rta506(root):
        return [f for f in run_suite(root, only=["drift"]).new
                if f.code == "RTA506"]

    assert rta506(tree("clean", [], [])) == []
    # (a) the engine vocabulary names a series nobody registers
    mutated = tree("mut-vocab",
                   [('("latency", "job"): '
                     '"rafiki_tpu_http_request_seconds"',
                     '("latency", "job"): '
                     '"rafiki_tpu_http_request_millis"')], [])
    assert any(f.anchor == "rafiki_tpu_http_request_millis"
               for f in rta506(mutated))
    # (b) a committed rules file references a renamed metric
    mutated = tree("mut-rules", [],
                   [("rafiki_tpu_serving_tenant_request_seconds",
                     "rafiki_tpu_serving_tenant_latency_seconds")])
    assert any(f.anchor == "rafiki_tpu_serving_tenant_latency_seconds"
               for f in rta506(mutated))
