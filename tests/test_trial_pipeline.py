"""Cross-trial dataset residency + pipelined trial lifecycle (r9).

Covers the two caches (host dataset cache in ``model/dataset.py``,
device staging cache in ``model/jax_model.py``) and the TrialRunner's
single-slot persist stage: LRU/byte-budget behavior, invalidation
rules (file rewrite, mesh change), the never-donated guarantee, the
counter-based zero-disk-load / zero-H2D regression for trial 2..N,
and persist ordering / drain / retroactive-error semantics.
"""

import threading
import time

import numpy as np
import pytest

import jax

from rafiki_tpu.advisor.base import Proposal
from rafiki_tpu.constants import BudgetOption, TrialStatus
from rafiki_tpu.model import dataset as mod_dataset
from rafiki_tpu.model import jax_model as mod_jax
from rafiki_tpu.model.base import BaseModel
from rafiki_tpu.model.dataset import (load_image_dataset,
                                      write_image_dataset_npz)
from rafiki_tpu.model.knobs import FixedKnob
from rafiki_tpu.model.logger import logger
from rafiki_tpu.models.feedforward import JaxFeedForward
from rafiki_tpu.observe import phases
from rafiki_tpu.parallel import build_mesh
from rafiki_tpu.store import MetaStore, ParamStore
from rafiki_tpu.worker.runner import TrialRunner


@pytest.fixture(autouse=True)
def _fresh_caches():
    mod_dataset.clear_dataset_cache()
    mod_jax.clear_stage_cache()
    yield
    mod_dataset.clear_dataset_cache()
    mod_jax.clear_stage_cache()


def _write_ds(path, n=12, seed=0, hw=8):
    rng = np.random.default_rng(seed)
    imgs = rng.integers(0, 255, (n, hw, hw, 1), dtype=np.uint8)
    labels = np.arange(n) % 3
    return write_image_dataset_npz(imgs, labels, str(path), 3)


# --- Device staging cache ---

def test_stage_cache_hits_and_mesh_change_invalidates(tmp_path):
    p = _write_ds(tmp_path / "a.npz")
    ds = load_image_dataset(p)
    mesh8 = build_mesh(jax.devices())
    d1, l1 = mod_jax.staged_dataset_arrays(p, ds, mesh8)
    d2, l2 = mod_jax.staged_dataset_arrays(p, ds, mesh8)
    assert d2 is d1 and l2 is l1  # resident across calls
    np.testing.assert_array_equal(np.asarray(d1), ds.images)
    np.testing.assert_array_equal(np.asarray(l1),
                                  ds.labels.astype(np.int32))
    # A different chip group is a different key: staged arrays are
    # never served across a mesh change.
    mesh4 = build_mesh(jax.devices()[:4])
    d3, _ = mod_jax.staged_dataset_arrays(p, ds, mesh4)
    assert d3 is not d1
    assert mod_jax.stage_cache_info()["entries"] == 2


def test_stage_cache_byte_budget_lru_eviction(tmp_path, monkeypatch):
    pa = _write_ds(tmp_path / "a.npz", seed=1)
    pb = _write_ds(tmp_path / "b.npz", seed=2)
    dsa, dsb = load_image_dataset(pa), load_image_dataset(pb)
    one = int(dsa.images.nbytes) + 4 * dsa.size
    monkeypatch.setenv(mod_jax.STAGE_CACHE_ENV, str(one + 8))
    mesh = build_mesh(jax.devices())
    da1, _ = mod_jax.staged_dataset_arrays(pa, dsa, mesh)
    mod_jax.staged_dataset_arrays(pb, dsb, mesh)  # evicts a (LRU)
    assert mod_jax.stage_cache_info()["entries"] == 1
    da2, _ = mod_jax.staged_dataset_arrays(pa, dsa, mesh)
    assert da2 is not da1  # a was re-staged after eviction


def test_stage_cache_disabled_by_zero_budget(tmp_path, monkeypatch):
    monkeypatch.setenv(mod_jax.STAGE_CACHE_ENV, "0")
    p = _write_ds(tmp_path / "a.npz")
    ds = load_image_dataset(p)
    mesh = build_mesh(jax.devices())
    d1, _ = mod_jax.staged_dataset_arrays(p, ds, mesh)
    d2, _ = mod_jax.staged_dataset_arrays(p, ds, mesh)
    assert d2 is not d1
    assert mod_jax.stage_cache_info()["entries"] == 0


def _write_tokens(path, n=1200, vocab=512, seed=0):
    from rafiki_tpu.model.dataset import write_token_dataset
    rng = np.random.default_rng(seed)
    return write_token_dataset(rng.integers(0, vocab, n), vocab,
                               str(path))


def test_token_stage_cache_hits_and_mesh_change_invalidates(tmp_path):
    from rafiki_tpu.model.dataset import load_token_dataset

    p = _write_tokens(tmp_path / "tok.npz")
    ds = load_token_dataset(p)
    mesh8 = build_mesh(jax.devices())
    d1 = mod_jax.staged_token_ids(p, ds, mesh8)
    d2 = mod_jax.staged_token_ids(p, ds, mesh8)
    assert d2 is d1  # resident across calls
    np.testing.assert_array_equal(np.asarray(d1),
                                  ds.ids.astype(np.int32))
    mesh4 = build_mesh(jax.devices()[:4])
    assert mod_jax.staged_token_ids(p, ds, mesh4) is not d1
    assert mod_jax.stage_cache_info()["entries"] == 2


def test_token_stage_cache_disabled_by_zero_budget(tmp_path,
                                                   monkeypatch):
    from rafiki_tpu.model.dataset import load_token_dataset

    monkeypatch.setenv(mod_jax.STAGE_CACHE_ENV, "0")
    p = _write_tokens(tmp_path / "tok.npz")
    ds = load_token_dataset(p)
    mesh = build_mesh(jax.devices())
    d1 = mod_jax.staged_token_ids(p, ds, mesh)
    assert mod_jax.staged_token_ids(p, ds, mesh) is not d1
    assert mod_jax.stage_cache_info()["entries"] == 0


def test_lm_eval_2_zero_disk_loads_and_zero_h2d(tmp_path):
    """The r9 trial-2 regression, cloned for the token/LM path: the
    SECOND evaluate of one dataset on one mesh pays no dataset parse
    (host cache hit) and no token H2D (staged stream hit — eval
    windows gather in-graph from device-computed iota indices), and
    both paths agree bit-for-bit with the unstaged host fallback."""
    from rafiki_tpu.models import JaxTransformerLM

    p = _write_tokens(tmp_path / "tok.npz")
    tiny = {"d_model": 256, "n_layers": 2, "seq_len": 256,
            "batch_size": 4, "learning_rate": 1e-2, "train_steps": 20,
            "vocab_size": 512, "quick_train": False}
    m = JaxTransformerLM(**JaxTransformerLM.validate_knobs(tiny))
    m._params = m._init_params()  # eval-only: training is not under test
    ds_b0 = phases.cache_counts("dataset")
    st_b0 = phases.cache_counts("stage")
    acc1 = m.evaluate(p)  # eval 1 pays the misses
    ds_b1 = phases.cache_counts("dataset")
    st_b1 = phases.cache_counts("stage")
    assert st_b1.get("miss", 0) == st_b0.get("miss", 0) + 1
    acc2 = m.evaluate(p)  # eval 2 must be fully resident
    ds_b2 = phases.cache_counts("dataset")
    st_b2 = phases.cache_counts("stage")
    assert acc2 == acc1
    assert ds_b2.get("miss", 0) == ds_b1.get("miss", 0)
    assert st_b2.get("miss", 0) == st_b1.get("miss", 0)
    assert st_b2.get("hit", 0) >= st_b1.get("hit", 0) + 1
    assert ds_b2.get("hit", 0) >= ds_b1.get("hit", 0) + 1
    # Oversized-stream fallback (host np.stack path) agrees exactly.
    import os

    os.environ["RAFIKI_TPU_STAGE_BYTES"] = "0"
    try:
        assert m.evaluate(p) == acc1
    finally:
        os.environ.pop("RAFIKI_TPU_STAGE_BYTES", None)
    # Cache DISABLED must also take the host path: staging would
    # device_put the whole stream uncached on every eval (review
    # finding) — stage counters must not move.
    os.environ[mod_jax.STAGE_CACHE_ENV] = "0"
    try:
        before = phases.cache_counts("stage")
        assert m.evaluate(p) == acc1
        assert phases.cache_counts("stage") == before
    finally:
        os.environ.pop(mod_jax.STAGE_CACHE_ENV, None)


FAST_KNOBS = {"hidden_layer_count": 1, "hidden_layer_units": 16,
              "learning_rate": 3e-3, "batch_size": 64, "max_epochs": 5}


def test_staged_arrays_never_donated_across_trainings(synth_image_data):
    """Train twice on the same dataset: the second training (and its
    eval) must find the FIRST training's staged buffers still valid —
    nothing may have donated or deleted them."""
    train_path, val_path = synth_image_data
    scores = []
    for _ in range(2):
        m = JaxFeedForward(**JaxFeedForward.validate_knobs(FAST_KNOBS))
        m.train(train_path)
        scores.append(float(m.evaluate(val_path)))
        m.destroy()
    assert mod_jax.stage_cache_info()["entries"] == 2  # train + val
    for data, labels in mod_jax._STAGE_CACHE.values():
        assert not data.is_deleted() and not labels.is_deleted()
        np.asarray(data)  # still readable end to end
    # identical data + seed -> the cached path reproduces the score
    assert scores[0] == pytest.approx(scores[1], abs=1e-6)


# --- Zero disk loads / zero full-dataset H2D for trial 2..N ---

class _FixedAdvisor:
    def __init__(self, knobs):
        self.knobs = knobs
        self.n = 0
        self.feedbacks = []

    def propose(self):
        self.n += 1
        return Proposal(trial_no=self.n, knobs=dict(self.knobs))

    def feedback(self, proposal, score):
        self.feedbacks.append((proposal.trial_no, score))


def test_trial_2_zero_disk_loads_and_zero_h2d(tmp_path,
                                              synth_image_data):
    train_path, val_path = synth_image_data
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    runner = TrialRunner(JaxFeedForward, _FixedAdvisor(FAST_KNOBS),
                         train_path, val_path, meta, params, "sub-r9",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 3})
    runner.run_one()  # trial 1 pays the misses
    ds_before = phases.cache_counts("dataset")
    st_before = phases.cache_counts("stage")
    runner.run_one()  # trial 2 must be fully resident
    ds_after = phases.cache_counts("dataset")
    st_after = phases.cache_counts("stage")
    assert ds_after.get("miss", 0) == ds_before.get("miss", 0)
    assert st_after.get("miss", 0) == st_before.get("miss", 0)
    # train + eval each hit both caches
    assert ds_after.get("hit", 0) >= ds_before.get("hit", 0) + 2
    assert st_after.get("hit", 0) >= st_before.get("hit", 0) + 2
    meta.close()
    params.close()


# --- Pipelined persist tail ---

CONFIG = {"width": FixedKnob(32)}


def _fake_model(events):
    class _Fake(BaseModel):
        @staticmethod
        def get_knob_config():
            return CONFIG

        def train(self, path, *, shared_params=None, **kw):
            events.append(("train", time.monotonic()))
            logger.log(msg="fake trained")
            self._params = {"w": np.asarray(1.0)}

        def evaluate(self, path):
            return 0.5

        def predict(self, queries):
            return [0 for _ in queries]

        def dump_parameters(self):
            return dict(self._params)

        def load_parameters(self, params):
            self._params = dict(params)

    return _Fake


def test_persist_pipeline_overlaps_orders_and_drains(tmp_path,
                                                     monkeypatch):
    """Trial N+1's work overlaps trial N's (slow) persistence, meta
    commits stay in trial order, the budget stays exact, and run()
    drains — no RUNNING rows survive it."""
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    events = []
    orig_save = params.save

    def slow_save(ps, **kw):
        events.append(("save_start", time.monotonic()))
        time.sleep(0.15)
        out = orig_save(ps, **kw)
        events.append(("save_end", time.monotonic()))
        return out

    monkeypatch.setattr(params, "save", slow_save)
    advisor = _FixedAdvisor({"width": 32})
    runner = TrialRunner(_fake_model(events), advisor, "tr", "va",
                         meta, params, "sub-pipe",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 3},
                         pipeline_persist=True)
    rows = runner.run()
    runner.close()
    # run() returns POST-drain rows: terminal status + params id, not
    # the pre-commit RUNNING snapshots run_one took.
    assert [r["status"] for r in rows] == [TrialStatus.COMPLETED] * 3
    assert all(r["params_id"] for r in rows)
    trials = sorted(meta.get_trials("sub-pipe"), key=lambda t: t["no"])
    assert [t["status"] for t in trials] == [TrialStatus.COMPLETED] * 3
    # budget exact despite the pipelined (meta-invisible) completions
    assert advisor.n == 3
    # strict per-trial ordering of the persisted commits
    finished = [t["finished_at"] for t in trials]
    assert finished == sorted(finished)
    # overlap actually happened: some trial trained while the previous
    # trial's save was still in flight
    saves = [(t0, next(t1 for n1, t1 in events
                       if n1 == "save_end" and t1 > t0))
             for n0, t0 in events if n0 == "save_start"]
    trains = [t for n, t in events if n == "train"]
    assert any(s0 < t < s1 for t in trains for s0, s1 in saves), \
        (events,)
    # buffered trial logs were flushed by the tail
    logs = meta.get_trial_logs(trials[0]["id"])
    assert any(r["record"].get("values", {}).get("msg") ==
               "fake trained" or "fake trained" in str(r["record"])
               for r in logs)
    meta.close()
    params.close()


def test_persist_failure_retroactively_errors_trial(tmp_path,
                                                    monkeypatch):
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))

    def bad_save(ps, **kw):
        raise RuntimeError("disk full (injected)")

    monkeypatch.setattr(params, "save", bad_save)
    advisor = _FixedAdvisor({"width": 32})
    runner = TrialRunner(_fake_model([]), advisor, "tr", "va", meta,
                         params, "sub-err",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 1},
                         pipeline_persist=True)
    row = runner.run_one()
    assert row is not None
    runner.drain_persist()
    runner.close()
    trial = meta.get_trials("sub-err")[0]
    assert trial["status"] == TrialStatus.ERRORED
    assert "disk full" in trial["error"]
    # the score was real: feedback reached the advisor anyway
    assert advisor.feedbacks == [(1, 0.5)]
    meta.close()
    params.close()


def test_stop_flag_drains_no_running_rows(tmp_path, monkeypatch):
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    orig_save = params.save
    monkeypatch.setattr(
        params, "save",
        lambda ps, **kw: (time.sleep(0.2), orig_save(ps, **kw))[1])
    stop = threading.Event()

    class _StopAfterOne(_FixedAdvisor):
        def feedback(self, proposal, score):
            super().feedback(proposal, score)
            stop.set()  # supervisor stops the job mid-persist

    runner = TrialRunner(_fake_model([]), _StopAfterOne({"width": 32}),
                         "tr", "va", meta, params, "sub-stop",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 50},
                         stop_flag=stop, pipeline_persist=True)
    runner.run()
    runner.close()
    trials = meta.get_trials("sub-stop")
    assert trials and all(t["status"] != TrialStatus.RUNNING
                          for t in trials)
    meta.close()
    params.close()


def test_repeated_tail_failures_trip_circuit_breaker(tmp_path,
                                                     monkeypatch):
    """A deterministic persist failure (disk full) must stop the loop
    via the consecutive-error breaker even though each run_one snapshot
    still said RUNNING — not spin forever against a trial-count budget
    that can never be satisfied."""
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    monkeypatch.setattr(
        params, "save",
        lambda ps, **kw: (_ for _ in ()).throw(
            RuntimeError("disk full (injected)")))
    runner = TrialRunner(_fake_model([]), _FixedAdvisor({"width": 32}),
                         "tr", "va", meta, params, "sub-breaker",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 50},
                         pipeline_persist=True)
    runner.run()  # must terminate
    runner.close()
    trials = meta.get_trials("sub-breaker")
    assert 3 <= len(trials) <= 5  # breaker fired, not the 50-budget
    assert all(t["status"] == TrialStatus.ERRORED for t in trials)
    meta.close()
    params.close()


def test_failed_final_tail_refunds_budget_slot(tmp_path, monkeypatch):
    """A persist failure on the trial that LOOKED like it satisfied the
    budget must refund its slot after the drain (pre-pipelining
    semantics): the loop runs a replacement trial instead of
    under-delivering MODEL_TRIAL_COUNT."""
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    orig_save = params.save
    calls = [0]

    def flaky_save(ps, **kw):
        calls[0] += 1
        if calls[0] == 1:
            raise RuntimeError("transient disk error (injected)")
        return orig_save(ps, **kw)

    monkeypatch.setattr(params, "save", flaky_save)
    runner = TrialRunner(_fake_model([]), _FixedAdvisor({"width": 32}),
                         "tr", "va", meta, params, "sub-refund",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 2},
                         pipeline_persist=True)
    runner.run()
    runner.close()
    trials = meta.get_trials("sub-refund")
    by_status = {}
    for t in trials:
        by_status[t["status"]] = by_status.get(t["status"], 0) + 1
    assert by_status.get(TrialStatus.COMPLETED) == 2, by_status
    assert by_status.get(TrialStatus.ERRORED) == 1, by_status
    meta.close()
    params.close()


# --- The persist stage owns the copy off the device ---

LM_TINY = {"d_model": 256, "n_layers": 2, "seq_len": 256, "batch_size": 4,
           "learning_rate": 1e-2, "train_steps": 8, "vocab_size": 512,
           "quick_train": False}
MOE_TINY = {"d_model": 64, "n_heads": 4, "n_layers": 3,
            "n_dense_layers": 1, "seq_len": 32, "vocab_size": 96,
            "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "ffn_dense": 160,
            "ffn_expert": 48, "n_experts": 8, "experts_per_token": 2,
            "experts_held": 4, "first_expert": 2, "n_shared_experts": 1,
            "routed_scaling": 2.5, "rope_theta": 32e6, "rms_eps": 1e-6,
            "mtp_depth": 1, "mtp_weight": 0.3, "bias_rate": 0.001,
            "batch_size": 8, "learning_rate": 1e-3, "train_steps": 4,
            "steps_per_dispatch": 2, "remat": "dots",
            "quick_train": False, "seed": 5}


def _pinned(base, knobs):
    """``base`` with every knob of ``knobs`` fixed; the class keeps each
    dict its ``dump_parameters`` handed on (and so the device leaves)."""
    class Pinned(base):
        dumps = []

        @staticmethod
        def get_knob_config():
            config = dict(base.get_knob_config())
            config.update({k: FixedKnob(v) for k, v in knobs.items()})
            return config

        def dump_parameters(self):
            out = super().dump_parameters()
            type(self).dumps.append(dict(out))
            return out

    return Pinned


def _lm_case(name):
    from rafiki_tpu.models import JaxLatentMoELM, JaxTransformerLM

    return {"dense": (JaxTransformerLM, LM_TINY, 512),
            "latent-moe": (JaxLatentMoELM, MOE_TINY, 96)}[name]


def test_lm_dump_hands_on_device_leaves_and_they_load_alike(tmp_path):
    from rafiki_tpu.models import JaxTransformerLM
    from rafiki_tpu.models.lm import _flat_names

    p = _write_tokens(tmp_path / "tok.npz", n=4000)
    knobs = JaxTransformerLM.validate_knobs(dict(LM_TINY, train_steps=20))
    m = JaxTransformerLM(**knobs)
    m._params = m._init_params()  # training is not under test
    dumped = m.dump_parameters()
    assert set(dumped) == {"embed", "lnf", "layers/ln1", "layers/ln2",
                           "layers/qkv", "layers/proj", "layers/w1",
                           "layers/w2"}
    named = _flat_names(m._params)
    for name, leaf in dumped.items():
        # the leaf itself, not a copy on the device or on the host
        assert isinstance(leaf, jax.Array) and leaf is named[name], name
    score = m.evaluate(p)
    m.destroy()  # the buffers live on through ``dumped``
    other = JaxTransformerLM(**knobs)
    other.load_parameters(dumped)
    assert other.evaluate(p) == score
    np.testing.assert_array_equal(
        np.asarray(other.dump_parameters()["layers/w2"]),
        np.asarray(dumped["layers/w2"]))


@pytest.mark.parametrize("case", ["dense", "latent-moe"])
def test_persist_thread_copies_and_the_store_reads_back_bit_for_bit(
        tmp_path, monkeypatch, case):
    from rafiki_tpu.observe import trace
    from rafiki_tpu.worker import runner as mod_runner

    base, knobs, vocab = _lm_case(case)
    model_class = _pinned(base, knobs)
    p = _write_tokens(tmp_path / "tok.npz", n=4000, vocab=vocab)
    calls = []
    to_host = mod_runner._to_host

    def watched(dumped):
        were = {k: isinstance(v, jax.Array) for k, v in dumped.items()}
        t0 = time.time()
        n_bytes = to_host(dumped)
        calls.append((threading.current_thread().name, were, t0,
                      time.time(), n_bytes))
        return n_bytes

    monkeypatch.setattr(mod_runner, "_to_host", watched)
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    trace.configure(str(tmp_path / "logs"))
    try:
        runner = TrialRunner(model_class, _FixedAdvisor({}), p, p, meta,
                             params, "sub-d2h", worker_id="w-d2h",
                             budget={BudgetOption.MODEL_TRIAL_COUNT: 1},
                             pipeline_persist=True)
        (row,) = runner.run()
        runner.close()
    finally:
        trace.configure(None)
    assert row["status"] == TrialStatus.COMPLETED
    (kept,) = model_class.dumps
    assert all(isinstance(v, jax.Array) for v in kept.values())
    if case == "latent-moe":
        assert {"state/sparse_bias", "state/mtp_bias"} <= set(kept)
    # one conversion, of every leaf, on the persist thread ...
    ((thread, were, t0, t1, n_bytes),) = calls
    assert thread.startswith("trial-persist")
    assert were == {name: True for name in kept}
    assert n_bytes == sum(v.nbytes for v in kept.values())
    # ... inside span ``persist``, which says what it cost
    (span,) = [s for s in trace.collect_trace(
        str(tmp_path / "logs"), row["id"])["spans"]
        if s["name"] == "trial.persist"]
    assert span["start_s"] <= t0 + 1e-3
    assert t1 <= span["start_s"] + span["dur_ms"] / 1e3 + 5e-3
    assert span["attrs"]["d2h_bytes"] == n_bytes
    assert 0 <= span["attrs"]["d2h_ms"] <= span["dur_ms"]
    # the store wrote host arrays at once (nothing rode its writer) ...
    assert params._writer is None
    # ... and holds the trained leaves' bytes
    stored = params.load(row["params_id"])
    assert set(stored) == set(kept)
    for name, leaf in kept.items():
        mine = np.asarray(leaf)
        assert stored[name].dtype == mine.dtype, name
        np.testing.assert_array_equal(stored[name], mine, err_msg=name)
    meta.close()
    params.close()


def _device_model(events, leaves=None):
    """A model whose parameters lie on the device; ``leaves`` collects a
    weak reference to each leaf it dumps."""
    import weakref

    import jax.numpy as jnp

    class _OnDevice(_fake_model(events)):
        def train(self, path, *, shared_params=None, **kw):
            super().train(path, shared_params=shared_params, **kw)
            self._params = {"w": jnp.arange(6.0).reshape(2, 3) + len(events),
                            "b": jnp.ones((4,), jnp.int32),
                            "meta": np.asarray([3, 4])}

        def dump_parameters(self):
            out = dict(self._params)
            if leaves is not None:
                leaves.extend(weakref.ref(v) for v in out.values()
                              if isinstance(v, jax.Array))
            return out

    return _OnDevice


def test_row_completes_only_after_file_and_index_row(tmp_path,
                                                     monkeypatch):
    """The write of trial 1 is held: its row stays RUNNING and nothing
    of it is in the store while trial 2 trains; when the row turns
    COMPLETED, the file and the index row are there."""
    import os

    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    release = threading.Event()
    flush = params._flush_to_disk
    seen = {}

    def held_flush(params_id, tree):
        seen.setdefault("first_write", params_id)
        assert release.wait(30)
        return flush(params_id, tree)

    monkeypatch.setattr(params, "_flush_to_disk", held_flush)
    completed = meta.mark_trial_completed

    def watched_commit(trial_id, score, params_id):
        seen.setdefault("at_commit", []).append(
            (os.path.exists(params._path(params_id)),
             params_id in params.session_params_ids("sub-order")))
        return completed(trial_id, score, params_id)

    monkeypatch.setattr(meta, "mark_trial_completed", watched_commit)
    events = []

    class _Watching(_device_model(events)):
        def train(self, path, **kw):
            super().train(path, **kw)
            if len(events) == 2:  # trial 2 trains, trial 1 is held
                (first,) = [t for t in meta.get_trials("sub-order")
                            if t["no"] == 1]
                seen["while_held"] = (
                    first["status"], first["params_id"],
                    os.listdir(params.params_dir),
                    params.session_params_ids("sub-order"))
                release.set()

    runner = TrialRunner(_Watching, _FixedAdvisor({"width": 32}), "tr",
                         "va", meta, params, "sub-order",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 2},
                         pipeline_persist=True)
    rows = runner.run()
    runner.close()
    status, params_id, files, index = seen["while_held"]
    assert status == TrialStatus.RUNNING and params_id is None
    assert not [f for f in files if f.endswith(".safetensors")]
    assert index == []
    assert seen["at_commit"] == [(True, True), (True, True)]
    assert [r["status"] for r in rows] == [TrialStatus.COMPLETED] * 2
    assert rows[0]["params_id"] == seen["first_write"]
    assert params._writer is None  # no write-behind for device leaves
    meta.close()
    params.close()


def test_no_device_leaf_outlives_the_drained_tail(tmp_path):
    import gc

    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    leaves = []
    runner = TrialRunner(_device_model([], leaves),
                         _FixedAdvisor({"width": 32}), "tr", "va", meta,
                         params, "sub-weak",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 2},
                         pipeline_persist=True)
    row = runner.run_one()
    runner.drain_persist()
    del row
    gc.collect()
    assert len(leaves) == 2 and all(ref() is None for ref in leaves)
    (trial,) = meta.get_trials("sub-weak")
    assert trial["status"] == TrialStatus.COMPLETED
    np.testing.assert_array_equal(
        params.load(trial["params_id"])["w"],
        np.arange(6.0, dtype=np.float32).reshape(2, 3) + 1)
    runner.close()
    meta.close()
    params.close()


def test_failed_copy_errors_the_trial_and_the_next_one_runs(tmp_path):
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    events = []

    class _FirstDumpIsGone(_device_model(events)):
        def dump_parameters(self):
            out = super().dump_parameters()
            if len(events) == 1:
                out["w"].delete()  # np.asarray of it raises
            return out

    advisor = _FixedAdvisor({"width": 32})
    runner = TrialRunner(_FirstDumpIsGone, advisor, "tr", "va", meta,
                         params, "sub-gone",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 1},
                         pipeline_persist=True)
    rows = runner.run()
    assert runner._persist.failure_count() == 1
    runner.close()
    assert [r["status"] for r in rows] == [TrialStatus.ERRORED,
                                           TrialStatus.COMPLETED]
    assert "deleted" in rows[0]["error"]
    assert rows[0]["params_id"] is None
    assert len(params.session_params_ids("sub-gone")) == 1
    # the score was real: the advisor heard of both
    assert [no for no, _ in advisor.feedbacks] == [1, 2]
    meta.close()
    params.close()


def test_counter_and_span_attrs_read_what_was_copied(tmp_path):
    from rafiki_tpu.observe import trace

    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    before = phases.dump_leaf_counts()
    trace.configure(str(tmp_path / "logs"))
    try:
        runner = TrialRunner(_device_model([]),
                             _FixedAdvisor({"width": 32}), "tr", "va",
                             meta, params, "sub-count",
                             budget={BudgetOption.MODEL_TRIAL_COUNT: 2},
                             pipeline_persist=True)
        rows = runner.run()
        runner.close()
        # a model that hands on host arrays only, and no stage at all
        for pipelined in (True, False):
            other = TrialRunner(_fake_model([]),
                                _FixedAdvisor({"width": 32}), "tr", "va",
                                meta, params, f"sub-host-{pipelined}",
                                budget={BudgetOption.MODEL_TRIAL_COUNT: 1},
                                pipeline_persist=pipelined)
            rows += other.run()
            other.close()
    finally:
        trace.configure(None)
    after = phases.dump_leaf_counts()
    # two trials of two device leaves (24 + 16 bytes) and one host leaf;
    # one trial of one host leaf; the runner with no stage counts nothing
    assert after["device"] - before["device"] == 4
    assert after["host"] - before["host"] == 2 + 1
    attrs = [next(s["attrs"] for s in trace.collect_trace(
        str(tmp_path / "logs"), row["id"])["spans"]
        if s["name"] == "trial.persist") for row in rows]
    assert [a.get("d2h_bytes") for a in attrs] == [40, 40, 0, None]
    assert all(a["d2h_ms"] >= 0 for a in attrs[:3])
    assert "d2h_ms" not in attrs[3]
    meta.close()
    params.close()
