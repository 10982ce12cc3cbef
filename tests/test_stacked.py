"""Compiled megabatch ensembles (r16): vmap-stacked same-family bins.

Unit layer: the congruence probe, stacked-vs-per-member numeric parity
across the zoo (f32 + int8), the dispatch-count gate (stacked mode is
STRICTLY fewer device dispatches than per-member mode for the same
burst), member-validity-mask fault isolation, in-place member restack,
and the zero-series guard for the disabled plane.

E2E layer: a real LocalPlatform packs two trials onto one worker,
registration advertises ``stacked: true``, and ``promote_trial``
surgically restacks ONE member in place — no new worker, the other
member stays resident.
"""

import time

import numpy as np
import pytest

pytest.importorskip("jax")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from rafiki_tpu.model.jax_model import (StackedMembers,  # noqa: E402
                                        stack_congruence, stack_members)
from rafiki_tpu.models.cnn import JaxCnn  # noqa: E402
from rafiki_tpu.models.feedforward import JaxFeedForward  # noqa: E402
from rafiki_tpu.models.vit import JaxViT  # noqa: E402
from rafiki_tpu.observe import metrics as obs_metrics  # noqa: E402
from rafiki_tpu.observe import wire as obs_wire  # noqa: E402
from rafiki_tpu.worker.inference import _PackedEnsemble  # noqa: E402

_SHAPES = {JaxFeedForward: (8, 8, 1), JaxCnn: (8, 8, 3),
           JaxViT: (8, 8, 1)}


def _member(cls, seed, n_classes=4, **knobs):
    """An initialized (untrained) model — serving only needs loaded
    variables, and random inits give distinct per-member outputs."""
    m = cls(**knobs)
    shape = _SHAPES[cls]
    m._ensure_module(n_classes, shape)
    extra = {k: jnp.asarray(v)
             for k, v in m.extra_apply_inputs().items()}
    variables = m._module.init(jax.random.key(seed),
                               jnp.zeros((1, *shape)), train=False,
                               **extra)
    m._variables = jax.tree.map(lambda a: np.asarray(a), variables)
    m._meta = {"n_classes": n_classes, "image_shape": list(shape)}
    return m


def _queries(shape, n=5, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.random((n, *shape)) * 255).astype(np.uint8)


def _stacked_rows(stacked, q, member):
    bucket = stacked.predict_bucket(q.shape[0], q.dtype)
    buf = np.zeros((bucket, *q.shape[1:]), q.dtype)
    buf[:q.shape[0]] = q
    handle = stacked.staged_submit(buf, q.shape[0])
    fins = stacked.member_finishers([handle])
    return np.asarray(fins[member]())


# --- Congruence probe -------------------------------------------------


def test_congruent_same_family_group_forms():
    ms = [_member(JaxFeedForward, s, hidden_layer_count=2,
                  hidden_layer_units=32) for s in (0, 1)]
    assert stack_congruence(ms) is None
    st = stack_members(ms)
    assert isinstance(st, StackedMembers) and st.n_members == 2


def test_different_trial_knobs_still_congruent():
    """Per-trial knobs are traced masks over one supernet — members
    with different widths/depths stack (the extras stack per member)."""
    a = _member(JaxFeedForward, 0, hidden_layer_count=1,
                hidden_layer_units=16)
    b = _member(JaxFeedForward, 1, hidden_layer_count=3,
                hidden_layer_units=128)
    assert stack_congruence([a, b]) is None


def test_incongruent_members_rejected_with_reason():
    ff = _member(JaxFeedForward, 0)
    cnn = _member(JaxCnn, 1)
    reason = stack_congruence([ff, cnn])
    assert reason is not None and "JaxCnn" in reason
    assert stack_members([ff, cnn]) is None
    # single member, unloaded member, sk-style (non-JaxModel) member
    assert stack_congruence([ff]) is not None

    class FakeSk:
        pass

    assert "not a JaxModel" in stack_congruence([ff, FakeSk()])
    other_classes = _member(JaxFeedForward, 2, n_classes=7)
    assert stack_congruence([ff, other_classes]) is not None


# --- Numeric parity across the zoo (f32 + int8) -----------------------


@pytest.mark.parametrize("cls,knob_sets", [
    (JaxFeedForward, [{"hidden_layer_count": 2,
                       "hidden_layer_units": 32},
                      {"hidden_layer_count": 1,
                       "hidden_layer_units": 16},
                      {"hidden_layer_count": 3,
                       "hidden_layer_units": 64}]),
    (JaxCnn, [{"width_16ths": 8}, {"width_16ths": 16}]),
])
@pytest.mark.parametrize("quant", [None, "int8"])
def test_stacked_vs_per_member_parity(cls, knob_sets, quant):
    """The acceptance gate: the ONE vmapped dispatch produces, per
    member, the same probabilities the member's own compiled runner
    produces — bit-close in f32, tolerance-bounded under int8 (both
    sides run the identical int8 graph, so they stay allclose)."""
    ms = [_member(cls, i, **k) for i, k in enumerate(knob_sets)]
    if quant:
        for m in ms:
            m.enable_serving_quant(quant)
    st = stack_members(ms)
    assert st is not None
    q = _queries(_SHAPES[cls])
    # f32 sides run one identical graph (vmapped vs not): tight. The
    # int8 side's dynamic per-row activation rounding may flip a unit
    # at a rounding boundary under vmap reassociation: int8 envelope.
    tol = dict(rtol=1e-3, atol=2e-2 if quant else 1e-4)
    for i, m in enumerate(ms):
        ref = np.asarray(m.predict_proba(q))
        got = _stacked_rows(st, q, i)
        np.testing.assert_allclose(got, ref, **tol)


def test_vit_stacked_parity_and_int8_accuracy():
    """The transformer zoo: stacked ViT members match their own
    runners, and the dequant-free int8 path (quantized_encoder_block)
    stays within the int8 accuracy envelope of f32."""
    ms = [_member(JaxViT, s, depth=2) for s in (0, 1)]
    q = _queries(_SHAPES[JaxViT], n=3)
    refs = [np.asarray(m.predict_proba(q)) for m in ms]
    st = stack_members(ms)
    assert st is not None
    for i in range(2):
        np.testing.assert_allclose(_stacked_rows(st, q, i), refs[i],
                                   rtol=1e-3, atol=1e-4)
    report = ms[0].enable_serving_quant("int8")
    # patchify conv (4-D) + per-block QKV/proj/FFN + head all int8
    assert report["n_int8"] >= 1 + 4 * 2 + 1
    p_q = np.asarray(ms[0].predict_proba(q))
    assert np.abs(p_q - refs[0]).max() < 0.05
    ms[0].enable_serving_quant("")


def test_cnn_int8_close_to_f32():
    """The conv zoo's dequant-free path (dynamic_int8_conv): int8
    serving stays within tolerance of f32."""
    m = _member(JaxCnn, 0, width_16ths=8)
    q = _queries(_SHAPES[JaxCnn])
    p32 = np.asarray(m.predict_proba(q))
    report = m.enable_serving_quant("int8")
    assert report["n_int8"] == 8  # 6 stage convs + 2 head denses
    p_q = np.asarray(m.predict_proba(q))
    assert np.abs(p32 - p_q).max() < 0.05
    assert (p32.argmax(-1) == p_q.argmax(-1)).all()


# --- Dispatch counting (the strictly-lower gate) ----------------------


def _count_dispatches(monkeypatch, ensemble, q):
    from rafiki_tpu.model import jax_model as jm

    calls = {"member": 0, "stacked": 0}
    orig_member = jm.JaxModel._dispatch_bucket
    orig_stacked = jm.StackedMembers._dispatch

    def member_spy(self, chunk, n):
        calls["member"] += 1
        return orig_member(self, chunk, n)

    def stacked_spy(self, chunk):
        calls["stacked"] += 1
        return orig_stacked(self, chunk)

    monkeypatch.setattr(jm.JaxModel, "_dispatch_bucket", member_spy)
    monkeypatch.setattr(jm.StackedMembers, "_dispatch", stacked_spy)
    bucket = ensemble.predict_bucket(q.shape[0], q.dtype)
    buf = np.zeros((bucket, *q.shape[1:]), q.dtype)
    buf[:q.shape[0]] = q
    preds = ensemble.predict_staged_submit(buf, q.shape[0])()
    monkeypatch.undo()
    return calls, preds


def test_stacked_burst_is_one_dispatch_per_member_is_n(monkeypatch):
    """The unit-level regression gate behind the ISSUE acceptance:
    the SAME burst costs len(members) device dispatches per-member
    and exactly ONE stacked — strictly lower for every real
    ensemble."""
    ms = [_member(JaxFeedForward, s) for s in (0, 1, 2)]
    q = _queries(_SHAPES[JaxFeedForward])
    permember = _PackedEnsemble(list(ms))
    calls_pm, preds_pm = _count_dispatches(monkeypatch, permember, q)
    assert calls_pm == {"member": 3, "stacked": 0}
    stacked = _PackedEnsemble(list(ms), stacked=stack_members(ms))
    calls_st, preds_st = _count_dispatches(monkeypatch, stacked, q)
    assert calls_st == {"member": 0, "stacked": 1}
    assert calls_st["stacked"] < calls_pm["member"]
    # ... and the served (pre-averaged) predictions agree.
    np.testing.assert_allclose(np.asarray(preds_st),
                               np.asarray(preds_pm),
                               rtol=1e-4, atol=1e-5)


def test_incongruent_bin_serves_per_member(monkeypatch):
    """The fallback contract: a bin the probe rejects serves exactly
    as before — per-member dispatches, correct ensemble output."""
    # same input shape, different head widths: truly incongruent
    ms = [_member(JaxFeedForward, 0),
          _member(JaxFeedForward, 1, n_classes=7)]
    assert stack_members(ms) is None
    ens = _PackedEnsemble(list(ms), stacked=stack_members(ms))
    q = _queries(_SHAPES[JaxFeedForward])
    calls, preds = _count_dispatches(monkeypatch, ens, q)
    assert calls == {"member": 2, "stacked": 0}
    assert len(preds) == q.shape[0]
    # mismatched vote widths ride a __members__ envelope, per member
    assert all("__members__" in p for p in preds)


# --- Member-validity mask (fault isolation) ---------------------------


def test_member_mask_drops_only_the_invalid_vote():
    ms = [_member(JaxFeedForward, s) for s in (0, 1, 2)]
    st = stack_members(ms)
    ens = _PackedEnsemble(list(ms), stacked=st)
    q = _queries(_SHAPES[JaxFeedForward])
    bucket = ens.predict_bucket(q.shape[0], q.dtype)
    buf = np.zeros((bucket, *q.shape[1:]), q.dtype)
    buf[:q.shape[0]] = q
    st.valid[1] = False
    preds = ens.predict_staged_submit(buf, q.shape[0])()
    assert ens.last_weight == 2
    refs = [np.asarray(m.predict_proba(q)) for m in ms]
    want = (refs[0] + refs[2]) / 2.0
    np.testing.assert_allclose(np.asarray(preds), want, rtol=1e-4,
                               atol=1e-5)
    st.valid[1] = True
    preds = ens.predict_staged_submit(buf, q.shape[0])()
    assert ens.last_weight == 3


# --- In-place restack -------------------------------------------------


def test_restack_swaps_one_member_others_stay_resident():
    ms = [_member(JaxFeedForward, s) for s in (0, 1)]
    st = stack_members(ms)
    q = _queries(_SHAPES[JaxFeedForward])
    ref0 = _stacked_rows(st, q, 0)
    runner_keys = set(st._runner_cache)
    assert runner_keys  # the parity fetch compiled a runner
    incoming = _member(JaxFeedForward, 9, hidden_layer_count=1,
                       hidden_layer_units=16)
    st.update_member(1, incoming)
    assert st.valid == [True, True]
    # no recompile: the runner cache still holds the same executables
    assert set(st._runner_cache) == runner_keys
    got1 = _stacked_rows(st, q, 1)
    np.testing.assert_allclose(
        got1, np.asarray(incoming.predict_proba(q)), rtol=1e-4,
        atol=1e-5)
    # member 0 untouched by the swap
    np.testing.assert_allclose(_stacked_rows(st, q, 0), ref0,
                               rtol=1e-6, atol=1e-7)


def test_restack_rejects_incongruent_member_before_touching_state():
    ms = [_member(JaxFeedForward, s) for s in (0, 1)]
    st = stack_members(ms)
    bad = _member(JaxFeedForward, 5, n_classes=7)
    with pytest.raises(ValueError, match="not congruent"):
        st.update_member(1, bad)
    assert st.valid == [True, True]  # nothing was masked
    q = _queries(_SHAPES[JaxFeedForward])
    np.testing.assert_allclose(
        _stacked_rows(st, q, 1),
        np.asarray(ms[1].predict_proba(q)), rtol=1e-4, atol=1e-5)


# --- Metric gating ----------------------------------------------------


@pytest.fixture()
def fresh_registry(monkeypatch):
    reg = obs_metrics.MetricsRegistry()
    monkeypatch.setattr(obs_metrics, "_registry", reg)
    obs_wire.reset_for_tests()
    yield reg
    obs_wire.reset_for_tests()


_STACKED_METRICS = ("rafiki_tpu_serving_stacked_dispatch_total",
                    "rafiki_tpu_serving_dispatches_per_query_ratio")


def test_stacked_off_zero_series(fresh_registry, monkeypatch):
    """RAFIKI_TPU_SERVING_STACKED=off ⇒ per-member serving and NO
    stacked series at all."""
    monkeypatch.setenv(obs_wire.STACKED_ENV, "off")
    obs_wire.reset_for_tests()
    assert not obs_wire.stacked_mode()
    ms = [_member(JaxFeedForward, s) for s in (0, 1)]
    ens = _PackedEnsemble(list(ms))  # knob off: no group ever forms
    q = _queries(_SHAPES[JaxFeedForward])
    ens.predict_submit([q[i] for i in range(q.shape[0])])()
    for name in _STACKED_METRICS:
        assert fresh_registry.find(name) is None, name


def test_stacked_on_counts_dispatches(fresh_registry, monkeypatch):
    monkeypatch.setenv(obs_wire.STACKED_ENV, "on")
    obs_wire.reset_for_tests()
    ms = [_member(JaxFeedForward, s) for s in (0, 1)]
    ens = _PackedEnsemble(list(ms), stacked=stack_members(ms))
    q = _queries(_SHAPES[JaxFeedForward])
    bucket = ens.predict_bucket(q.shape[0], q.dtype)
    buf = np.zeros((bucket, *q.shape[1:]), q.dtype)
    buf[:q.shape[0]] = q
    ens.predict_staged_submit(buf, q.shape[0])()
    c = fresh_registry.find(_STACKED_METRICS[0])
    assert c is not None and c.value(mode="stacked") == 1
    g = fresh_registry.find(_STACKED_METRICS[1])
    assert g is not None and 0 < g.value() <= 1.0 / q.shape[0] + 1e-9
    # a masked-out group falls back per-member and counts it
    ens.stacked.valid = [False, False]
    ens.predict_staged_submit(buf, q.shape[0])()
    assert c.value(mode="fallback") == 2


def test_unknown_stacked_spelling_fails_safe_off(monkeypatch):
    monkeypatch.setenv(obs_wire.STACKED_ENV, "onn")
    assert obs_wire.stacked_mode() is False
    monkeypatch.setenv(obs_wire.STACKED_ENV, "on")
    assert obs_wire.stacked_mode() is True


# --- E2E: packed deploy advertises stacked, promote restacks ----------


def test_e2e_packed_bin_stacked_promote_restack(tmp_path,
                                                synth_image_data):
    import requests

    from rafiki_tpu.cache import Cache, encode_payload
    from rafiki_tpu.constants import (BudgetOption, TaskType,
                                      UserType)
    from rafiki_tpu.model import load_image_dataset
    from rafiki_tpu.platform import LocalPlatform

    platform = LocalPlatform(workdir=str(tmp_path / "plat"),
                             supervise_interval=0)
    try:
        train_path, val_path = synth_image_data
        dev = platform.admin.create_user("st@x.c", "pw",
                                         UserType.MODEL_DEVELOPER)
        model = platform.admin.create_model(
            dev["id"], "ff-st", TaskType.IMAGE_CLASSIFICATION,
            "rafiki_tpu.models.feedforward:JaxFeedForward")
        job = platform.admin.create_train_job(
            dev["id"], "ff-st", TaskType.IMAGE_CLASSIFICATION,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 3},
            train_path, val_path)
        assert platform.admin.wait_until_train_job_done(job["id"],
                                                        timeout=600)
        best = platform.admin.get_best_trials(job["id"], max_count=3)
        assert len(best) == 3
        # One worker owning the node's whole slice packs both trials
        # (the compiled-megabatch deploy shape).
        inf = platform.admin.create_inference_job(
            dev["id"], job["id"], max_models=2,
            chips_per_worker=platform.services.allocator.n_chips)
        cache = Cache(platform.bus)
        deadline = time.time() + 120
        while not cache.running_workers(inf["id"]) and \
                time.time() < deadline:
            time.sleep(0.2)
        info = cache.running_worker_info(inf["id"])
        assert len(info) == 1, "expected ONE packed worker"
        (worker_id, reg), = info.items()
        served = set(str(reg["trial_id"]).split(","))
        assert served == {best[0]["id"], best[1]["id"]}
        assert reg.get("stacked") is True

        host = platform.admin.get_inference_job(
            inf["id"])["predictor_host"]
        ds = load_image_dataset(val_path)
        q = encode_payload(ds.images[0])

        def predict():
            r = requests.post(f"http://{host}/predict",
                              json={"query": q}, timeout=180)
            assert r.status_code == 200, r.text
            return r.json()["prediction"]

        assert "error" not in str(predict())[:40]

        # Surgical promote: replace ONE member of the packed bin.
        incoming, outgoing = best[2], best[1]
        res = platform.admin.promote_trial(
            inf["id"], incoming["id"],
            replace_trial_id=outgoing["id"])
        assert res["restacked_service_ids"] == [worker_id]
        assert res["new_service_id"] is None  # no launch: in-place
        assert res["stopped_service_ids"] == []
        info = cache.running_worker_info(inf["id"])
        assert set(info) == {worker_id}, "the SAME worker serves on"
        served = set(str(info[worker_id]["trial_id"]).split(","))
        assert served == {best[0]["id"], incoming["id"]}
        # meta mapping row followed the bin
        rows = platform.services.active_inference_workers(inf["id"])
        assert {r["trial_id"] for r in rows} == \
            {str(info[worker_id]["trial_id"])}
        assert "error" not in str(predict())[:40]

        # promoting an already-served member is still rejected
        with pytest.raises(ValueError, match="already served"):
            platform.admin.promote_trial(
                inf["id"], incoming["id"],
                replace_trial_id=best[0]["id"])
    finally:
        platform.shutdown()
