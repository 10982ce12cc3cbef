"""JaxTransformerLM — the flagship causal LM (the model of the
benchmark's ``lm14-*`` cells).

No reference counterpart (upstream Rafiki has no LM task — SURVEY.md
§2); the model exists to give the platform a compute-dense training
citizen for the ≥90%-utilization north star. Tests run tiny shapes on
the CPU mesh (the Pallas kernels run in interpreter mode there).
"""

import jax
import numpy as np
import pytest

from rafiki_tpu.datasets import make_synthetic_token_dataset
from rafiki_tpu.model.dataset import (load_token_dataset,
                                      write_token_dataset)
from rafiki_tpu.model.logger import logger
from rafiki_tpu.models import JaxTransformerLM

TINY = {"d_model": 256, "n_layers": 2, "seq_len": 256, "batch_size": 4,
        "learning_rate": 1e-2, "train_steps": 200, "vocab_size": 512,
        "quick_train": False}


@pytest.fixture(scope="module")
def token_data(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("lm")
    return make_synthetic_token_dataset(
        str(tmp), n_train=1 << 15, n_val=1 << 12, vocab_size=512,
        branching=2)


def test_token_dataset_roundtrip(tmp_path):
    ids = np.arange(1000, dtype=np.int32) % 64
    path = write_token_dataset(ids, 64, str(tmp_path / "toks"))
    ds = load_token_dataset(path)
    assert ds.vocab_size == 64 and ds.size == 1000
    assert np.array_equal(ds.ids, ids)


def test_token_dataset_rejects_out_of_range(tmp_path):
    path = write_token_dataset(np.asarray([0, 99], np.int32), 64,
                               str(tmp_path / "bad"))
    with pytest.raises(ValueError, match="out of range"):
        load_token_dataset(path)


@pytest.mark.slow
def test_lm_learns_markov_chain(token_data):
    """A branching-2 order-1 chain: a working LM reaches ~1/2 next-token
    accuracy (the chain's ceiling); chance is 1/512. Also covers the
    dump/load roundtrip and the LM-scoring predict contract (a
    chain-consistent continuation must outscore random tokens)."""
    train_path, val_path = token_data
    m = JaxTransformerLM(**JaxTransformerLM.validate_knobs(TINY))
    m.train(train_path)
    acc = m.evaluate(val_path)
    assert acc > 0.35, acc

    params = m.dump_parameters()
    m2 = JaxTransformerLM(**JaxTransformerLM.validate_knobs(TINY))
    m2.load_parameters(params)
    assert abs(m2.evaluate(val_path) - acc) < 1e-6

    ds = load_token_dataset(val_path)
    real = ds.ids[:129].tolist()
    rng = np.random.default_rng(0)
    fake = rng.integers(0, 512, size=129).tolist()
    score_real, score_fake = m2.predict([real, fake])
    assert score_real > score_fake + 1.0, (score_real, score_fake)
    m2.destroy()
    m.destroy()


def test_lm_quick_train_cap(token_data):
    """quick_train caps the step budget (the AutoML trial contract)."""
    train_path, _ = token_data
    knobs = dict(TINY, train_steps=5000, quick_train=True)
    # trial_steps is a FixedKnob (production policy: 30); the cap
    # MECHANISM — min(train_steps, trial_steps) — is what's under
    # test, so override it below validation and keep the 1-core CPU
    # mesh inside the tier-1 wall-clock budget (16 = two fused
    # dispatches at steps_per_dispatch=8, covering the tail-chunk
    # path too).
    m = JaxTransformerLM(**dict(JaxTransformerLM.validate_knobs(knobs),
                                trial_steps=16))
    records = []
    prev = logger.current_sink()
    logger.set_sink(records.append)
    try:
        m.train(train_path)
    finally:
        logger.set_sink(prev)
    steps = [r["values"]["step"] for r in records
             if r.get("type") == "values"
             and "step" in r.get("values", {})]
    assert steps and max(steps) == 16, steps  # capped, not 5000
    assert m.dump_parameters()
    m.destroy()


def _train_on_group(indices, train_path, val_path, knobs):
    """One trial's life with its chip group bound to this thread;
    returns (devices of the freshly initialised params, logged losses)."""
    from rafiki_tpu.parallel import ChipGroup

    ChipGroup(indices=indices).bind_to_thread()
    records = []
    prev = logger.current_sink()
    logger.set_sink(records.append)
    try:
        # trial_steps / steps_per_dispatch are FixedKnobs (30 / 8); two
        # dispatches of 4 steps keep the interpreted kernel inside the
        # tier-1 budget.
        m = JaxTransformerLM(**dict(
            JaxTransformerLM.validate_knobs(knobs), trial_steps=8,
            steps_per_dispatch=4))
        born_on = {d.id for leaf in jax.tree.leaves(m._init_params())
                   for d in leaf.devices()}
        # Nothing may be staged through a chip outside the group (the
        # first four-chip run found Adam's state born on device 0): any
        # device-to-device copy in the trial's life — train, evaluate,
        # the served copy's load and predict — raises.
        with jax.transfer_guard_device_to_device("disallow_explicit"):
            m.train(train_path)
            acc = m.evaluate(val_path)
            served = JaxTransformerLM(**m.knobs)
            served.load_parameters(m.dump_parameters())
            assert abs(served.evaluate(val_path) - acc) < 1e-6
            score, = served.predict([list(range(1, 40))])
            assert np.isfinite(score) and score < 0.0
        for model in (m, served):
            on = {d.id for leaf in jax.tree.leaves(model._params)
                  for d in leaf.devices()}
            assert on == set(indices), on
            model.destroy()
    finally:
        logger.set_sink(prev)
        ChipGroup.unbind_thread()
    losses = [r["values"]["loss"] for r in records
              if r.get("type") == "values" and "loss" in r["values"]]
    return born_on, losses


def test_lm_dp4_group_matches_dp1_and_stays_on_its_chips(token_data):
    """The flash kernel runs inside shard_map over the group's dp axis
    (Mosaic kernels are never auto-partitioned; interpret mode here),
    and the parameters are born on the group's own devices: a dp=4
    group (chips 4-7) and a one-chip group (chip 2) train the same
    global batch to the same loss, and neither touches device 0."""
    train_path, val_path = token_data
    knobs = dict(TINY, quick_train=True)
    born4, loss4 = _train_on_group((4, 5, 6, 7), train_path, val_path,
                                   knobs)
    born1, loss1 = _train_on_group((2,), train_path, val_path, knobs)
    assert born4 == {4, 5, 6, 7} and born1 == {2}
    assert len(loss4) == len(loss1) == 2
    np.testing.assert_allclose(loss4, loss1, rtol=5e-3)


# --- evaluate: reduced on the device, one step-cached program ---

#: Small enough that a forward in the kernels' interpreter takes a
#: second; no test below trains.
EVAL_TINY = {"d_model": 256, "n_layers": 2, "seq_len": 256,
             "batch_size": 4, "learning_rate": 1e-3, "train_steps": 20,
             "vocab_size": 512, "quick_train": False}
N_POS = 15 * 256  # positions evaluated: 15 whole windows of the stream


@pytest.fixture(scope="module")
def eval_stream(tmp_path_factory):
    """4,000 tokens over 8 symbols (15 windows of 256): token 0 is a
    target often enough that an all-ties arg-max scores above zero."""
    ids = np.random.default_rng(7).integers(0, 8, size=4000,
                                            dtype=np.int32)
    return write_token_dataset(
        ids, 512, str(tmp_path_factory.mktemp("lm_eval") / "val"))


def _untrained(zeroed=False, seed=0, **knobs):
    """A model with parameters but no training: fresh from the
    initialiser, or all zero (then every logit is 0 and every column
    ties). ``seed`` is a FixedKnob, so it goes in below validation."""
    m = JaxTransformerLM(**dict(JaxTransformerLM.validate_knobs(
        dict(EVAL_TINY, **knobs)), seed=seed))
    m._params = m._init_params()
    params = m.dump_parameters()
    if zeroed:
        params = {k: np.zeros_like(v) for k, v in params.items()}
    m.load_parameters(params)
    return m


def _host_argmax_score(m, val_path):
    """The evaluation as it was before it moved onto the device: all
    the logits to the host, a NumPy arg-max there."""
    ds = load_token_dataset(val_path)
    t = m._dims()["t"]
    n_win = max(1, min(16, (ds.size - 1) // t))
    ids = np.stack([ds.ids[i * t:i * t + t + 1] for i in range(n_win)])
    logits = np.asarray(jax.jit(m._forward)(
        m._ensure_params_dev(), ids[:, :-1].astype(np.int32)))
    assert logits.shape == (n_win, t, 512)
    return float((logits.argmax(-1) == ids[:, 1:]).mean())


@pytest.mark.parametrize("zeroed", [False, True],
                         ids=["init_params", "all_logits_tie"])
@pytest.mark.parametrize("stage_bytes", [None, "1"],
                         ids=["staged_stream", "over_staging_budget"])
def test_lm_evaluate_equals_host_argmax(eval_stream, monkeypatch,
                                        stage_bytes, zeroed):
    """Exactly (``==``) the score a host arg-max over the same logits
    gives, from either source of the windows; with every column tied
    both take index 0, so the score is the share of targets that are
    token 0."""
    from rafiki_tpu.observe import phases

    if stage_bytes is not None:
        monkeypatch.setenv("RAFIKI_TPU_STAGE_BYTES", stage_bytes)
    m = _untrained(zeroed=zeroed)
    staged = phases.cache_counts("stage")
    score = m.evaluate(eval_stream)
    grew = sum(phases.cache_counts("stage").values()) \
        - sum(staged.values())
    assert grew == (1 if stage_bytes is None else 0)  # which branch ran
    assert score == _host_argmax_score(m, eval_stream)
    if zeroed:
        ids = load_token_dataset(eval_stream).ids
        assert score == float((ids[1:N_POS + 1] == 0).mean()) > 0.05
    m.destroy()


def _step_cache_growth(fn):
    from rafiki_tpu.observe import phases

    before = phases.cache_counts("step")
    out = fn()
    after = phases.cache_counts("step")
    return out, {e: after.get(e, 0) - before.get(e, 0)
                 for e in ("hit", "miss")}


def test_lm_evaluate_program_shared_across_trials(eval_stream):
    """Two trials of one shape that differ in every knob the forward
    does not read share the evaluation's program: one step-cache miss,
    then one hit, and the second evaluation neither traces nor lowers
    nor compiles anything."""
    import jax.monitoring

    from rafiki_tpu.model.jax_model import clear_step_cache

    clear_step_cache()
    first = _untrained(learning_rate=1e-3, train_steps=20, seed=0)
    second = _untrained(learning_rate=7e-4, train_steps=40, seed=3)
    _, grew = _step_cache_growth(lambda: first.evaluate(eval_stream))
    assert grew == {"miss": 1, "hit": 0}

    events = []

    def listener(event, seconds, **kw):
        if event.startswith("/jax/core/compile/"):
            events.append((event, kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        score, grew = _step_cache_growth(
            lambda: second.evaluate(eval_stream))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert grew == {"miss": 0, "hit": 1}
    assert events == []
    # Shared program, own parameters: another seed, another score than
    # the first instance's parameters give through the same program.
    assert score == _host_argmax_score(second, eval_stream)
    assert first._predict_fn is None and second._predict_fn is None
    first.destroy()
    second.destroy()


@pytest.mark.parametrize("knob, value", [("d_model", 512),
                                         ("n_layers", 3)])
def test_lm_evaluate_program_keyed_on_shape(eval_stream, knob, value):
    """What the forward does read is in the key: another width or
    depth misses."""
    base, other = _untrained(), _untrained(**{knob: value})
    base.evaluate(eval_stream)           # in the cache from here on
    _, grew = _step_cache_growth(lambda: base.evaluate(eval_stream))
    assert grew == {"miss": 0, "hit": 1}
    _, grew = _step_cache_growth(lambda: other.evaluate(eval_stream))
    assert grew["miss"] == 1
    base.destroy()
    other.destroy()


def test_lm_evaluate_fetches_one_scalar(eval_stream, monkeypatch):
    """All that ``evaluate`` brings to the host is the count: one
    explicit ``jax.device_get`` of an int32 scalar. Implicit fetches
    are disallowed around the whole call (on the CPU backend a guard on
    host reads of host memory has nothing to catch, so the fetches are
    counted as well), and no logits program is built."""
    m = _untrained()
    fetched = []
    device_get = jax.device_get

    def spy(x):
        fetched.append(x)
        return device_get(x)

    with monkeypatch.context() as patched, \
            jax.transfer_guard_device_to_host("disallow"):
        patched.setattr(jax, "device_get", spy)
        score = m.evaluate(eval_stream)
    assert len(fetched) == 1 and isinstance(fetched[0], jax.Array)
    assert fetched[0].shape == () and fetched[0].dtype == np.int32
    assert score == int(fetched[0]) / N_POS
    assert m._predict_fn is None
    m.destroy()
