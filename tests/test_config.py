"""Typed node config (SURVEY.md §5 "Config / flag system" rebuild)."""

import pytest

from rafiki_tpu.config import NodeConfig


def test_defaults_validate():
    cfg = NodeConfig.from_env(env={})
    assert cfg.port == 3000 and cfg.workdir == "./rafiki_workdir"
    # serving_pipeline defaults to None = auto (workers measure their
    # sync latency at startup and decide).
    assert cfg.serving_pipeline is None and not cfg.checkpoint_trials
    assert cfg.n_chips is None and cfg.bus_uri == ""


def test_env_parsing_and_types():
    cfg = NodeConfig.from_env(env={
        "RAFIKI_TPU_PORT": "8080",
        "RAFIKI_TPU_N_CHIPS": "4",
        "RAFIKI_TPU_BUS_URI": "tcp://10.0.0.1:6380",
        "RAFIKI_TPU_SUPERVISE_INTERVAL": "2.5",
        "RAFIKI_TPU_SERVING_PIPELINE": "0",
        "RAFIKI_TPU_CKPT": "1",
        "RAFIKI_TPU_TRACE_DIR": "/tmp/traces",
    })
    assert cfg.port == 8080 and cfg.n_chips == 4
    assert cfg.bus_uri == "tcp://10.0.0.1:6380"
    assert cfg.supervise_interval == 2.5
    assert cfg.serving_pipeline is False
    assert cfg.checkpoint_trials is True
    assert cfg.trace_dir == "/tmp/traces"


def test_cli_overrides_beat_env():
    cfg = NodeConfig.from_env(env={"RAFIKI_TPU_PORT": "8080"},
                              port=9090, workdir=None)
    assert cfg.port == 9090                   # explicit override wins
    assert cfg.workdir == "./rafiki_workdir"  # None = not given


def test_validation_errors():
    with pytest.raises(ValueError):
        NodeConfig.from_env(env={}, port=-1)
    with pytest.raises(ValueError):
        NodeConfig.from_env(env={}, n_chips=0)
    with pytest.raises(ValueError):
        NodeConfig.from_env(env={}, log_level="loud")
    with pytest.raises(ValueError):
        NodeConfig.from_env(env={}, bus_uri="redis://x")
    with pytest.raises(ValueError):
        NodeConfig.from_env(env={}, coordinator="h:1")  # partial triple
    with pytest.raises(ValueError):
        NodeConfig.from_env(env={"RAFIKI_TPU_PORT": "not-a-number"})


def test_multihost_triple_accepted():
    cfg = NodeConfig.from_env(env={}, coordinator="h:1234",
                              num_processes=2, process_id=0)
    assert cfg.coordinator == "h:1234"


def test_apply_env_round_trip(monkeypatch):
    # setenv (not delenv) so monkeypatch restores the pre-test state
    # even though apply_env() mutates os.environ during the test.
    monkeypatch.setenv("RAFIKI_TPU_SERVING_PIPELINE", "1")
    monkeypatch.setenv("RAFIKI_TPU_CKPT", "")
    cfg = NodeConfig.from_env(env={}, serving_pipeline=False,
                              checkpoint_trials=True)
    cfg.apply_env()
    import os

    assert os.environ["RAFIKI_TPU_SERVING_PIPELINE"] == "0"
    assert os.environ["RAFIKI_TPU_CKPT"] == "1"
    # Workers constructed now resolve the node's validated values.
    from rafiki_tpu.bus import MemoryBus
    from rafiki_tpu.worker.inference import InferenceWorker

    w = InferenceWorker("s", "j", "t", None, None, MemoryBus())
    assert w.pipeline is False


def test_serving_microbatch_knobs(monkeypatch):
    """Micro-batcher knobs: env parsing, validation bounds, and the
    apply_env -> PredictorService handoff."""
    cfg = NodeConfig.from_env(env={})
    assert cfg.serving_microbatch is True
    assert cfg.serving_fill_window == 0.005
    assert cfg.serving_max_inflight == 2
    cfg = NodeConfig.from_env(env={
        "RAFIKI_TPU_SERVING_MICROBATCH": "0",
        "RAFIKI_TPU_SERVING_FILL_WINDOW": "0.02",
        "RAFIKI_TPU_SERVING_MAX_BATCH": "256",
        "RAFIKI_TPU_SERVING_MAX_INFLIGHT": "3",
        "RAFIKI_TPU_SERVING_QUEUE_CAP": "512",
    })
    assert cfg.serving_microbatch is False
    assert cfg.serving_fill_window == 0.02
    assert cfg.serving_max_batch == 256
    assert cfg.serving_max_inflight == 3
    assert cfg.serving_queue_cap == 512
    with pytest.raises(ValueError, match="serving_fill_window"):
        NodeConfig.from_env(env={}, serving_fill_window=-0.1)
    with pytest.raises(ValueError, match="serving_max_batch"):
        NodeConfig.from_env(env={}, serving_queue_cap=0)

    # apply_env exports the knobs; a PredictorService constructed after
    # (in-process or spawned) resolves the node's validated values.
    for var in ("RAFIKI_TPU_SERVING_MICROBATCH",
                "RAFIKI_TPU_SERVING_FILL_WINDOW",
                "RAFIKI_TPU_SERVING_MAX_BATCH",
                "RAFIKI_TPU_SERVING_MAX_INFLIGHT",
                "RAFIKI_TPU_SERVING_QUEUE_CAP"):
        monkeypatch.setenv(var, "unset-sentinel")
    NodeConfig.from_env(env={}, serving_fill_window=0.03,
                        serving_queue_cap=128).apply_env()
    import os

    assert os.environ["RAFIKI_TPU_SERVING_MICROBATCH"] == "1"
    assert os.environ["RAFIKI_TPU_SERVING_FILL_WINDOW"] == "0.03"
    assert os.environ["RAFIKI_TPU_SERVING_QUEUE_CAP"] == "128"
    from rafiki_tpu.bus import MemoryBus
    from rafiki_tpu.predictor.app import PredictorService

    svc = PredictorService("s", "j", None, MemoryBus())
    assert svc.batcher is not None
    assert svc.batcher.fill_window == 0.03
    assert svc.batcher.queue_cap == 128


def test_from_config_platform(tmp_path):
    from rafiki_tpu.platform import LocalPlatform

    cfg = NodeConfig.from_env(env={}, workdir=str(tmp_path / "n"),
                              supervise_interval=0.0)
    p = LocalPlatform.from_config(cfg)
    try:
        assert p.workdir == str(tmp_path / "n")
        assert p.app is None
    finally:
        p.shutdown()


def test_trial_lifecycle_knobs(monkeypatch):
    """r9: the residency-cache budgets + advisor prefetch are NodeConfig
    fields with env parity and apply_env export."""
    cfg = NodeConfig.from_env(env={
        "RAFIKI_TPU_DATASET_CACHE_BYTES": "1024",
        "RAFIKI_TPU_STAGE_CACHE_BYTES": "0",
        "RAFIKI_TPU_ADVISOR_PREFETCH": "off",
    })
    assert cfg.dataset_cache_bytes == 1024
    assert cfg.stage_cache_bytes == 0
    assert cfg.advisor_prefetch is False
    import os

    # setenv sentinels (not delenv): apply_env() mutates os.environ
    # outside monkeypatch's bookkeeping, and a delenv of an ABSENT var
    # registers no undo — the non-default budgets below (stage cache 0!)
    # would otherwise leak into every later test in the session.
    for var in ("RAFIKI_TPU_DATASET_CACHE_BYTES",
                "RAFIKI_TPU_STAGE_CACHE_BYTES",
                "RAFIKI_TPU_ADVISOR_PREFETCH"):
        monkeypatch.setenv(var, "unset-sentinel")
    cfg.apply_env()
    assert os.environ["RAFIKI_TPU_DATASET_CACHE_BYTES"] == "1024"
    assert os.environ["RAFIKI_TPU_STAGE_CACHE_BYTES"] == "0"
    assert os.environ["RAFIKI_TPU_ADVISOR_PREFETCH"] == "0"
    # the caches honor the exported budgets immediately
    from rafiki_tpu.model.dataset import dataset_cache_budget

    assert dataset_cache_budget() == 1024
    with pytest.raises(ValueError):
        NodeConfig(dataset_cache_bytes=-1).validate()


def test_every_nodeconfig_knob_is_documented():
    """Tier-1 gate: scripts/check_knob_docs.py asserts every NodeConfig
    env knob appears in docs/ops.md, so a new knob can't silently go
    undocumented."""
    import os
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo_root, "scripts", "check_knob_docs.py"),
         repo_root],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "documented in docs/ops.md" in proc.stdout


def test_knob_docs_check_catches_missing(tmp_path):
    import os
    import shutil
    import subprocess
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    (tmp_path / "rafiki_tpu").mkdir()
    shutil.copy(os.path.join(repo_root, "rafiki_tpu", "config.py"),
                tmp_path / "rafiki_tpu" / "config.py")
    (tmp_path / "docs").mkdir()
    # RAFIKI_TPU_METRICS_PORT present must NOT count as documenting
    # RAFIKI_TPU_METRICS (delimited-token match, not substring).
    (tmp_path / "docs" / "ops.md").write_text(
        "| `RAFIKI_TPU_WORKDIR` | only one knob documented |\n"
        "also mentions RAFIKI_TPU_METRICS_PORT in passing\n")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(repo_root, "scripts", "check_knob_docs.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    assert "RAFIKI_TPU_DATASET_CACHE_BYTES" in proc.stdout
    assert "NodeConfig.metrics (RAFIKI_TPU_METRICS)" in proc.stdout
    assert "RAFIKI_TPU_WORKDIR" not in proc.stdout
