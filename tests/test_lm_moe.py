"""JaxLatentMoELM (latent attention + sparse experts, one chip's share
+ a multi-token-prediction module) against the benchmark's plain
float32 reference (``benchmarks/reference/joyai_flash.py``), at tiny
widths on the CPU: hidden 64, 4 heads of 16 + 8 / 16, ranks 48 / 32,
8 experts top-2, one dense + two sparse blocks + the multi-token module.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafiki_tpu.constants import BudgetOption
from rafiki_tpu.datasets import make_synthetic_token_dataset
from rafiki_tpu.model.knobs import FixedKnob
from rafiki_tpu.models import JaxLatentMoELM
from rafiki_tpu.models import lm_moe
from rafiki_tpu.models.lm import _flat_names, _weights
from rafiki_tpu.observe import phases
from rafiki_tpu.ops import moe as moe_ops
from rafiki_tpu.ops import (flash_attention, held_experts_swiglu,
                            naive_attention, sigmoid_topk_gates)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _bench_module(*parts):
    """A file of the benchmark as a module of its own, imported as
    ``run.py`` imports it (``benchmarks/`` on the path while it
    loads: the reference takes its recipe code from ``harness``)."""
    path = os.path.join(BENCH, *parts)
    name = "bench_test_" + "_".join(parts).replace(".py", "").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.path.insert(0, BENCH)
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


reference = _bench_module("reference", "joyai_flash.py")
compare = _bench_module("compare.py")

#: The tiny model's knobs, and the same sizes under the published keys
#: the reference reads (``dims_of``).
TINY = {"d_model": 64, "n_heads": 4, "n_layers": 3, "n_dense_layers": 1,
        "seq_len": 32, "vocab_size": 96, "q_lora_rank": 48,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "ffn_dense": 160, "ffn_expert": 48,
        "n_experts": 8, "experts_per_token": 2, "experts_held": 4,
        "first_expert": 2, "n_shared_experts": 1, "routed_scaling": 2.5,
        "rope_theta": 32e6, "rms_eps": 1e-6, "mtp_depth": 1,
        "mtp_weight": 0.3, "bias_rate": 0.001, "batch_size": 8,
        "learning_rate": 1e-3, "train_steps": 4, "steps_per_dispatch": 2,
        "remat": "dots", "quick_train": False, "seed": 5}
TINY_CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "max_position_embeddings": 32,
    "vocab_size": 96, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 160, "moe_intermediate_size": 48,
    "router_experts": 8, "num_experts_per_tok": 2, "n_routed_experts": 4,
    "first_expert": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "rope_theta": 32e6,
    "rms_norm_eps": 1e-6, "num_nextn_predict_layers": 1,
    "mtp_loss_weight": 0.3, "bias_update_rate": 0.001}
RECIPE = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4,
          "warmup_div": 10, "start_factor": 0.1, "end_factor": 0.1}
DIMS = reference.dims_of(TINY_CONFIG)


class TinyMoELM(JaxLatentMoELM):
    @staticmethod
    def get_knob_config():
        knobs = dict(JaxLatentMoELM.get_knob_config())
        knobs.update({name: FixedKnob(v) for name, v in TINY.items()})
        return knobs


def _program_names(flat):
    """The reference's flat names as the program dumps them."""
    out = {}
    for name, value in flat.items():
        if name.endswith("_bias"):
            out[f"state/{name}"] = value
        elif "/" in name:
            out[f"blocks/{name}"] = value
        else:
            out[name] = value
    return out


def _reference_names(dumped):
    return {name.split("/", 1)[-1]: np.asarray(value)
            for name, value in dumped.items()}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def seeded():
    """(model holding the reference's seeded weights with a non-zero
    router bias, those weights under the reference's names, windows)."""
    flat = dict(reference.init_params(5, DIMS))
    # A draw on which the bf16 program and the float32 reference pick
    # the same experts for every token: a near-tied top-k choice that
    # flips is a discrete change of that token's output (and, through
    # attention, of every later one's), which no tolerance describes.
    rng = np.random.default_rng(11)
    for name in ("sparse_bias", "mtp_bias"):
        flat[name] = jnp.asarray(
            0.05 * rng.standard_normal(flat[name].shape), jnp.float32)
    model = TinyMoELM(**TINY)
    model.load_parameters(_program_names(
        {k: np.asarray(v) for k, v in flat.items()}))
    win = jnp.asarray(rng.integers(0, 96, size=(2, 33)), jnp.int32)
    return model, flat, win


@pytest.fixture
def float32(monkeypatch):
    """The program's matmul operands in float32: against the float32
    reference the equations then hold leaf by leaf to 1e-4, and no
    near-tied top-k choice flips. (Programs are traced anew under it:
    the tests below call the functions, or train a class of their
    own.)"""
    monkeypatch.setattr(lm_moe, "COMPUTE", jnp.float32)


def test_initialiser_is_the_reference_s(seeded):
    mine = _reference_names(_flat_names(TinyMoELM(**TINY)._init_params()))
    theirs = reference.init_params(5, DIMS)
    assert set(mine) == set(theirs)
    for name in theirs:
        np.testing.assert_array_equal(mine[name], np.asarray(theirs[name]),
                                      err_msg=name)


def test_logits_match_the_reference_in_bfloat16(seeded):
    model, flat, win = seeded
    mine = model._forward(model._params, win[:, :-1])
    assert mine.dtype == jnp.float32 and mine.shape == (2, 32, 96)
    # bf16 operands against float32, over 3 blocks and the head
    assert _rel(mine, reference.forward(flat, win[:, :-1], DIMS)) < 2e-2


def test_logits_match_the_reference_in_float32(seeded, float32):
    model, flat, win = seeded
    mine = model._forward(model._params, win[:, :-1])
    assert _rel(mine, reference.forward(flat, win[:, :-1], DIMS)) < 1e-4


def test_total_loss_and_every_gradient_leaf_match_the_reference(
        seeded, float32):
    model, flat, win = seeded
    s, remat, mesh = model._forward_spec()
    (loss, (_, counts, state)), grads = jax.value_and_grad(
        lm_moe._moe_lm_loss, has_aux=True)(
            _weights(model._params), model._params["state"], win, s, remat,
            mesh)
    ref_weights = {k: v for k, v in flat.items()
                   if not reference.is_state(k)}
    ref_state = {k: v for k, v in flat.items() if reference.is_state(k)}
    (ref_loss, ref_counts), ref_grads = jax.value_and_grad(
        lambda w: reference.loss_and_counts({**w, **ref_state}, win, DIMS),
        has_aux=True)(ref_weights)
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    mine = _reference_names(_flat_names(grads))
    assert set(mine) == set(ref_grads)
    for name, theirs in ref_grads.items():
        assert mine[name].shape == theirs.shape, name
        assert _rel(mine[name], theirs) < 1e-3, (
            name, _rel(mine[name], theirs))
    # tokens x k, over two sparse blocks of T and the module's T - 1
    routed = 2 * 2 * (2 * 32 + 31)
    assert float(counts[0] + counts[1]) == routed
    total = np.concatenate([np.asarray(ref_counts["sparse_bias"]),
                            np.asarray(ref_counts["mtp_bias"])[None]])
    assert float(counts[0]) == total[:, 2:6].sum()
    assert float(counts[2]) == total[:, 2:6].max(-1).sum()
    # the bias steps against the load: b + gamma * sign(mean(c) - c)
    want = np.asarray(flat["mtp_bias"]) + 0.001 * np.sign(
        total[2].mean() - total[2])
    np.testing.assert_allclose(np.asarray(state["mtp_bias"]), want,
                               atol=1e-7)


def _layer(rng, e=8, d=64, f=48):
    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]),
                           jnp.float32)

    return {"router": r(d, e), "e_gate": r(e, d, f), "e_up": r(e, d, f),
            "e_down": r(e, f, d), "s_gate": r(d, f), "s_up": r(d, f),
            "s_down": r(f, d)}


def _share(p, first, held):
    return {k: (v[first:first + held] if k.startswith("e_") else v)
            for k, v in p.items()}


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(held, float32):
    """The parts that the E / held shares compute, the shared expert
    (which every chip computes alike) counted once, sum to what the
    uncut reference layer gives; selection and the gates' normalisation
    run over all E in every share."""
    rng = np.random.default_rng(held)
    p = _layer(rng)
    bias = jnp.asarray(0.05 * rng.standard_normal(8), jnp.float32)
    u = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)

    def f32_dot(a, b):
        return jnp.matmul(a, b, precision="highest")

    whole, counts = reference.moe(u, p, bias, dict(DIMS), f32_dot,
                                  first=0, held=8)
    shared = reference.swiglu(u, p["s_gate"], p["s_up"], p["s_down"],
                              f32_dot)
    total = np.zeros(whole.shape, np.float64)
    for first in range(0, 8, held):
        s = dict(TinyMoELM(**TINY)._dims(), first=first, held=held)
        part, c = lm_moe._moe_ffn(u, _share(p, first, held), bias, s)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts))
        total += np.asarray(part, np.float64) - np.asarray(shared)
        # and the reference, given the same share, gives the same part
        theirs, _ = reference.moe(u, _share(p, first, held), bias,
                                  dict(DIMS), f32_dot, first, held)
        assert _rel(part, theirs) < 1e-4
    assert float(counts.sum()) == 2 * 24 * 2
    assert _rel(total + np.asarray(shared), whole) < 1e-4


def test_no_token_is_dropped_when_the_router_sends_all_to_two_experts(
        monkeypatch):
    """A bias that forces every token onto experts 3 and 4: each gets
    all 40 tokens (five times the uniform share, three blocks of 16
    rows), the gates sum to the scale, and every token's output is its
    two experts' SwiGLU."""
    monkeypatch.setattr(moe_ops, "BLOCK", 16)
    rng = np.random.default_rng(7)
    p = _layer(rng)
    x = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    bias = jnp.zeros((8,), jnp.float32).at[jnp.array([3, 4])].set(10.0)
    gates, chosen = sigmoid_topk_gates(x, p["router"], bias, k=2,
                                       scale=2.5)
    assert np.asarray(chosen).sum(0).tolist() == [0, 0, 0, 40, 40, 0, 0, 0]
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-6)
    out = held_experts_swiglu(
        x.astype(jnp.bfloat16), gates[:, 2:6], chosen[:, 2:6],
        p["e_gate"][2:6], p["e_up"][2:6], p["e_down"][2:6])
    want = sum(
        np.asarray(gates[:, e:e + 1]) * np.asarray(reference.swiglu(
            x, p["e_gate"][e], p["e_up"][e], p["e_down"][e],
            lambda a, b: jnp.matmul(a, b, precision="highest")))
        for e in (3, 4))
    assert np.abs(np.asarray(out)).sum(-1).min() > 0  # no zero row
    assert _rel(out, want) < 1.5e-2


def test_held_experts_gradients_match_a_masked_sum(monkeypatch):
    """The hand-written backward loop (x, gates, the three weight
    stacks) against autodiff through a dense masked sum, at a block
    size that leaves ragged last blocks and an expert with no token."""
    monkeypatch.setattr(moe_ops, "BLOCK", 8)
    rng = np.random.default_rng(3)
    p = _layer(rng, e=6)
    x = jnp.asarray(rng.standard_normal((50, 64)), jnp.float32)
    bias = jnp.zeros((6,), jnp.float32).at[1].set(-10.0)  # expert 1 idle
    ct = jnp.asarray(rng.standard_normal((50, 64)), jnp.float32)

    def mine(x, router, wg, wu, wd):
        gates, chosen = sigmoid_topk_gates(x, router, bias, k=2, scale=2.5)
        return (held_experts_swiglu(x.astype(jnp.bfloat16), gates[:, :4],
                                    chosen[:, :4], wg, wu, wd)
                * ct).sum()

    def dense(x, router, wg, wu, wd):
        gates, _ = sigmoid_topk_gates(x, router, bias, k=2, scale=2.5)
        out = sum(gates[:, e:e + 1] * reference.swiglu(
            x, wg[e], wu[e], wd[e], lambda a, b: jnp.matmul(
                a, b, precision="highest")) for e in range(4))
        return (out * ct).sum()

    args = (x, p["router"], p["e_gate"][:4], p["e_up"][:4],
            p["e_down"][:4])
    got = jax.grad(mine, argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(dense, argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(("x", "router", "e_gate", "e_up", "e_down"),
                          got, want):
        assert _rel(a, b) < 2.5e-2, (name, _rel(a, b))
    assert float(jnp.abs(got[2][1]).max()) == 0.0  # the idle expert


@pytest.mark.parametrize("grad", [False, True], ids=["values", "grads"])
def test_flash_path_with_v_narrower_than_q(grad):
    """MLA's attention through the flash kernels (the interpreter
    here): v is 2/3 of q's width and goes in at its own, o comes back
    at it; the scale is 1/sqrt(q's width)."""
    rng = np.random.default_rng(11)
    q, k = (jnp.asarray(rng.standard_normal((1, 2, 40, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, 2, 40, 16)), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def naive(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(24)
        mask = jnp.tril(jnp.ones((40, 40), bool))
        return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1) @ v

    if not grad:
        np.testing.assert_allclose(flash(q, k, v), naive(q, k, v),
                                   atol=2e-5, rtol=2e-5)
        # the repo's naive attention agrees, and on the padded problem
        padded = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, 8)))
        for got in (naive_attention(q, k, v, causal=True),
                    naive_attention(q, k, padded, causal=True)[..., :16]):
            np.testing.assert_allclose(got, naive(q, k, v), atol=2e-5,
                                       rtol=2e-5)
        return
    ct = jnp.asarray(rng.standard_normal((1, 2, 40, 16)), jnp.float32)
    got = jax.grad(lambda *a: (flash(*a) * ct).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (naive(*a) * ct).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


@pytest.fixture(scope="module")
def token_data(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("moe_lm")
    return make_synthetic_token_dataset(
        str(tmp), n_train=1 << 12, n_val=1 << 8, vocab_size=96,
        branching=2)


def test_four_optimizer_steps_follow_the_reference(token_data, float32):
    """What the benchmark compares, at tiny size: the logged losses of
    a 4-step trial (two dispatches of two; batch 8, a row a device of
    the tests' dp=8 mesh) and the norm of every leaf's
    change, router biases included, against the reference's own run of
    the trial from the seed."""
    from rafiki_tpu.model.dataset import load_token_dataset
    from rafiki_tpu.model.logger import logger

    train_path, _ = token_data
    logged = []
    logger.set_sink(lambda record: logged.append(record))
    class Float32LM(TinyMoELM):  # its train chunk is traced under float32
        pass

    try:
        model = Float32LM(**TINY)
        before = phases.moe_counts()
        model.train(train_path)
    finally:
        logger.set_sink(None)
    losses = [r["values"]["loss"] for r in logged
              if "loss" in (r.get("values") or {})]
    ids = load_token_dataset(train_path).ids
    first, final, step_losses = reference.train(
        ids, 5, DIMS, RECIPE, steps=4, batch=8, per_dispatch=2,
        learning_rate=1e-3)
    # host float64 holding float32 values: compare.py copies nothing
    assert first["head"].dtype == final["head"].dtype == np.float64
    assert (final["head"] == final["head"].astype(np.float32)).all()
    assert compare.loss_gap(losses, compare.chunk_means(step_losses, 2)) \
        < 1e-5
    gap, where = compare.dparam_gap(
        _reference_names(model.dump_parameters()), final, first,
        DIMS["layers"])
    assert gap < 2e-3, (gap, where)
    # the biases moved, by gamma a step, and as the reference's did
    mine = _reference_names(model.dump_parameters())
    assert np.abs(mine["sparse_bias"]).max() <= 4 * 0.001 + 1e-9
    assert np.abs(mine["sparse_bias"]).max() > 0
    np.testing.assert_allclose(mine["sparse_bias"], final["sparse_bias"],
                               atol=1e-7)
    # held + absent = tokens x k over every sparse block of every step
    after = phases.moe_counts()
    grew = {k: after[k] - before[k] for k in after}
    assert grew["held"] + grew["absent"] == 4 * 8 * 2 * (2 * 32 + 31)
    assert 0 < grew["held"] < grew["held"] + grew["absent"]
    assert grew["busiest"] * 4 >= grew["held"]  # imbalance >= 1
    model.destroy()


def test_the_control_is_held_to_the_cells_limits(tmp_path, monkeypatch):
    """``selftest/control_joyai.py``: the reference's own trial is
    saved (float32 on disk: its values are float32's), the float8
    control is compared with it by ``compare.py``'s numbers and held to
    the workload's limits, as the driver holds the program: limits
    under its readings make it ``correct`` false, wide ones true."""
    monkeypatch.syspath_prepend(BENCH)
    control = _bench_module("selftest", "control_joyai.py")
    config = dict(TINY_CONFIG, reference="joyai_flash", recipe=RECIPE,
                  data={"generator": "tokens", "n_train": 4096,
                        "branching": 2},
                  knobs={"batch_size": 2, "steps_per_dispatch": 2})

    def workload(**limits):
        return {"job": {"fixed": {"train_steps": 4,
                                  "learning_rate": 1e-3}},
                "limits": limits}

    tight = workload(loss_gap=1e-4, dparam_gap=1e-3)
    assert control.stage(config, tight, 7, "f32", "", str(tmp_path)) is None
    saved = np.load(tmp_path / "final.npz")
    assert saved["head"].dtype == np.float32
    out = control.stage(config, tight, 7, "fp8", "", str(tmp_path))
    assert out["correct"] is False
    assert 1e-4 < out["loss_gap"] < 1 and 1e-3 < out["dparam_gap"] < 1
    assert out["leaf"].split("[")[0] in saved.files
    wide = control.stage(config, workload(loss_gap=1.0, dparam_gap=1.0), 7,
                         "fp8", "", str(tmp_path))
    assert wide == dict(out, correct=True)


def test_a_job_of_three_trials_compiles_once(token_data, tmp_path):
    """The class rides the shared trainer's step cache: over a job of
    three congruent trials the train chunk and the evaluation's program
    are built once, 2 misses + 4 hits."""
    from rafiki_tpu.advisor.base import Proposal
    from rafiki_tpu.store import MetaStore, ParamStore
    from rafiki_tpu.worker.runner import TrialRunner

    class JobLM(TinyMoELM):  # a class nothing has compiled for
        pass

    class Advisor:
        n = 0

        def propose(self):
            self.n += 1
            return Proposal(trial_no=self.n, knobs={})

        def feedback(self, proposal, score):
            pass

    train_path, val_path = token_data
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "params"))
    runner = TrialRunner(JobLM, Advisor(), train_path, val_path, meta,
                         params, "sub-moe", worker_id="w-moe",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 3},
                         pipeline_persist=True)
    before = phases.cache_counts("step")
    rows = runner.run()
    runner.close()
    after = phases.cache_counts("step")
    assert [r["status"] for r in rows] == ["COMPLETED"] * 3
    assert {k: after.get(k, 0) - before.get(k, 0)
            for k in ("miss", "hit")} == {"miss": 2, "hit": 4}
    stored = params.load(rows[-1]["params_id"])
    assert "state/sparse_bias" in stored and "blocks/mtp/eh" in stored
    meta.close()
    params.close()


def test_predict_scores_through_the_shared_forward(seeded):
    model, flat, win = seeded
    query = np.asarray(win[0, :20]).tolist()
    (score,) = model.predict([query])
    logits = reference.forward(flat, win[:1, :19], DIMS)
    logp = jax.nn.log_softmax(logits, -1)
    want = float(np.mean([logp[0, i, query[i + 1]] for i in range(19)]))
    assert abs(score - want) < 2e-2 * abs(want)
    assert model.predict([]) == [] and model.predict([[3]]) == [0.0]


def test_generation_is_refused_with_one_clear_error(seeded):
    model, _, _ = seeded
    with pytest.raises(NotImplementedError, match="latent.*sparse-expert"):
        model.make_generator(page_size=16)


def test_dump_and_load_round_trip_the_nested_tree(seeded):
    model, _, win = seeded
    dumped = model.dump_parameters()
    assert {"embed", "head", "lnf", "blocks/dense/q_a",
            "blocks/sparse/e_gate", "blocks/mtp/eh", "state/sparse_bias",
            "state/mtp_bias"} <= set(dumped)
    assert dumped["blocks/sparse/e_gate"].shape == (2, 4, 64, 48)
    other = TinyMoELM(**TINY)
    other.load_parameters(dumped)
    np.testing.assert_array_equal(
        np.asarray(other._forward(other._params, win[:, :-1])),
        np.asarray(model._forward(model._params, win[:, :-1])))


_UPLOADED = '''
from rafiki_tpu.model import FixedKnob
from rafiki_tpu.models import JaxLatentMoELM


class UploadedMoELM(JaxLatentMoELM):
    @staticmethod
    def get_knob_config():
        knobs = dict(JaxLatentMoELM.get_knob_config())
        knobs.update({name: FixedKnob(v) for name, v in %r.items()})
        return knobs
'''


def test_deploy_with_generation_on_is_refused_with_the_reason(
        token_data, tmp_path, monkeypatch):
    """Uploaded as a template and trained by ``create_train_job`` like
    any other class; ``create_inference_job`` with generative serving
    on fails AT the deploy, with the one error that names what
    ``lm_generate.py`` lacks, and leaves no inference job behind."""
    from rafiki_tpu.constants import TaskType, UserType
    from rafiki_tpu.platform import LocalPlatform

    train_path, val_path = token_data
    platform = LocalPlatform(workdir=str(tmp_path / "plat"), http=False,
                             supervise_interval=0)
    try:
        dev = platform.admin.create_user("moe@x.c", "pw",
                                         UserType.MODEL_DEVELOPER)
        model = platform.admin.create_model(
            dev["id"], "moe-lm", TaskType.LANGUAGE_MODELING,
            "UploadedMoELM",
            model_source=_UPLOADED % dict(TINY, train_steps=2))
        job = platform.admin.create_train_job(
            dev["id"], "moe-app", TaskType.LANGUAGE_MODELING,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 1},
            train_path, val_path)
        assert platform.admin.wait_until_train_job_done(job["id"],
                                                        timeout=600)
        (best,) = platform.admin.get_best_trials(job["id"], max_count=1)
        assert best["status"] == "COMPLETED"
        monkeypatch.setenv("RAFIKI_TPU_SERVING_GENERATE", "1")
        with pytest.raises(ValueError, match="latent.*sparse-expert"):
            platform.admin.create_inference_job(dev["id"], job["id"],
                                                max_models=1)
        assert platform.meta.get_inference_job_by_train_job(
            job["id"]) is None
    finally:
        platform.shutdown()
