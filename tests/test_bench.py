"""bench.py record semantics — the driver-facing contract.

The driver parses bench.py's one JSON line into BENCH_r{N}.json; these
tests pin the parts a human later reads off that artifact: platform-
correct vs_baseline (a CPU value compared against a TPU baseline must
read as null, not a 9x win), per-config error records that never lose
the sweep, and a utilization probe that chains rather than swallows
whatever log sink the surrounding harness installed.
"""

import json
import subprocess
import sys

import pytest

import bench
from rafiki_tpu.model.logger import logger


def test_emit_nulls_vs_baseline_off_platform():
    # Tests run on CPU (conftest), which is not in BASELINE_PLATFORMS —
    # even a metric with a recorded baseline must read null.
    rec = bench._emit("automl_trials_per_hour", 2468.0, "u")
    assert rec["platform"] == "cpu"
    assert rec["vs_baseline"] is None


def test_emit_ratio_on_baseline_platform(monkeypatch):
    monkeypatch.setattr(bench, "BASELINE_PLATFORMS", ("cpu",))
    monkeypatch.setitem(bench.BASELINES, "cpu", {"m": 268.0})
    assert bench._emit("m", 536.0, "u")["vs_baseline"] == 2.0
    # no recorded baseline = this run establishes it
    assert bench._emit("m2", 536.0, "u")["vs_baseline"] == 1.0


def test_baselines_are_per_channel():
    # One channel remains: the platform jax reports for the real chip.
    # Every sweep metric has an entry there (a figure, or None = the
    # next chip run establishes it), so no record's vs_baseline can be
    # computed against a figure from anywhere else.
    assert bench.BASELINE_PLATFORMS == ("tpu",)
    assert set(bench.BASELINES) == {"tpu"}
    sweep_metrics = {bench._CONFIGS[name][1]
                     for name in bench._SWEEP_ORDER}
    assert sweep_metrics == set(bench.BASELINES["tpu"])


def test_emit_labels_chip_util_basis(monkeypatch):
    rec = bench._emit("m", 1.0, "u", chip_util=0.5)
    assert rec["chip_util_basis"] == "calibrated-cpu-roofline"
    monkeypatch.setattr(bench, "BASELINE_PLATFORMS", ("cpu",))
    rec = bench._emit("m", 1.0, "u", chip_util=0.5)
    assert rec["chip_util_basis"] == "spec-peak"


def test_util_probe_chains_and_restores_prior_sink():
    seen = []
    logger.set_sink(seen.append)
    try:
        with bench._UtilProbe() as probe:
            logger.log(chip_util=0.42, loss=1.0)
        assert probe.values == [0.42]
        # The pre-existing sink saw the record too...
        assert seen and seen[0]["values"]["chip_util"] == 0.42
        # ...and is back in place after the probe exits.
        logger.log(loss=0.5)
        assert len(seen) == 2
    finally:
        logger.set_sink(None)


def test_run_config_captures_systemexit_as_error_record():
    rec = bench._run_config("attention", "cpu")  # needs TPU -> SystemExit
    assert rec["metric"] == "flash_attention_tflops"
    assert rec["value"] == 0.0 and rec["vs_baseline"] is None
    assert "error" in rec and "seconds" in rec


def test_sweep_emits_one_line_with_per_config_records():
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    # "attn" is a deliberate typo: unknown names must be skipped with a
    # note, not crash the sweep before its one JSON line. The subset
    # under test is deliberately CHEAP (attention refuses the CPU at
    # once; analysis is a ~seconds gate run) — this test pins the
    # sweep/record CONTRACT, not any config's own measurement, and
    # the tier-1 budget cannot afford a full multitenant train here
    # (r13: the suite runs within ~2% of its timeout). The child gets
    # an explicit CPU request: a test never reaches for the chip.
    env.update({"JAX_PLATFORMS": "cpu",
                "RAFIKI_TPU_BENCH_CONFIGS": "attn,attention,analysis",
                "RAFIKI_TPU_BENCH_IDLE_MAX_WAIT": "2"})
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--config", "sweep"],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["sweep"] is True
    assert set(rec["configs"]) == {"attention", "analysis"}
    assert "ignoring unknown config name(s) ['attn']" in out.stderr
    # attention needs the TPU, so on the explicit CPU it is an error
    # record; analysis is the gate config: value = NEW findings, 0 on a
    # clean tree.
    for sub in rec["configs"].values():
        assert "seconds" in sub
    assert rec["configs"]["analysis"]["value"] == 0.0
    assert "error" not in rec["configs"]["analysis"]
    attn = rec["configs"]["attention"]
    assert attn["platform"] == "cpu" and "error" in attn
    assert attn["value"] == 0.0 and attn["vs_baseline"] is None


def test_analysis_config_records_finding_counts():
    """The static-analysis gate smoke: one record, value = NEW findings
    (0 on a clean tree), per-code counts folded in for the bench
    artifact. Runs the real CLI subprocess, like production."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--config", "analysis"],
        capture_output=True, text=True, timeout=300, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.splitlines()[-1])
    assert rec["metric"] == "analysis_new_findings"
    assert rec["value"] == 0.0 and rec["exit_code"] == 0
    assert rec["unit"] == "findings"
    assert all(k.startswith("RTA") for k in rec["counts_per_code"])
    assert set(rec["by_status"]) <= {"baselined", "waived", "new"}
    assert rec["files"] > 50 and rec["checkers"]


@pytest.mark.slow
@pytest.mark.slower
def test_sweep_heavy_configs_run_on_cpu_mesh():
    """VERDICT r3 item 6: the sweep's heavy configs (serving,
    multitenant) execute END-TO-END through the real _run_config path
    on the 8-virtual-device CPU mesh — every record parses, carries no
    error, and nulls vs_baseline (CPU is not a baseline channel).
    Before this, configs 2-7 had only ever run through the stubbed
    contract test; a wedge in their platform plumbing would surface
    only on the next chip run."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "RAFIKI_TPU_BENCH_CONFIGS": "serving,multitenant"})
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--config", "sweep"],
        capture_output=True, text=True, timeout=1800, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    for name in ("serving", "multitenant"):
        sub = rec["configs"][name]
        assert "error" not in sub, (name, sub)
        assert sub["value"] > 0
        assert sub["platform"] == "cpu"
        assert sub["vs_baseline"] is None


def test_lm_serving_config_registered_outside_sweep():
    """lm-serving is a counter-judged gate (docs/serving.md
    "Benchmarking it"), runnable via --config but never part of the
    platform sweep — same policy as analysis/chaos/autoscale."""
    fn, metric, unit = bench._CONFIGS["lm-serving"]
    assert metric == "lm_serving_tokens_per_sec" and unit == "tokens/s"
    assert fn is bench.main_lm_serving
    assert "lm-serving" not in bench._SWEEP_ORDER
