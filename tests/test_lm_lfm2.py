"""JaxLfm2MoeLM (gated short convolutions and grouped-query attention in
one layer pattern over sparse experts, one chip's share) against the
benchmark's plain float32 reference (``benchmarks/reference/
lfm2_moe.py``), at tiny widths on the CPU: hidden 64, 4 query / 2
key-value heads of 16, 8 experts top-2 of which 4 are held, the
benchmark cell's own pattern conv, attention, conv, conv, conv with one
leading dense layer.
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
from rafiki_tpu.models import JaxLfm2MoeLM, lm_lfm2, lm_moe
from rafiki_tpu.models.lm import _flat_names, _weights
from rafiki_tpu.observe import phases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


def _bench_module(*parts):
    """A file of the benchmark as a module of its own, imported as
    ``run.py`` imports it (``benchmarks/`` on the path while it
    loads: the reference takes its recipe code from ``harness``)."""
    path = os.path.join(BENCH, *parts)
    name = "bench_lfm2_test_" + "_".join(parts).replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.path.insert(0, BENCH)
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(BENCH)
    return module


reference = _bench_module("reference", "lfm2_moe.py")
compare = _bench_module("compare.py")

PATTERN = ["conv", "full_attention", "conv", "conv", "conv"]
#: The tiny model's knobs, and the same sizes under the published keys
#: the reference reads (``dims_of``).
TINY = {"d_model": 64, "n_heads": 4, "n_kv_heads": 2, "n_layers": 5,
        "layer_types": PATTERN, "n_dense_layers": 1, "conv_taps": 3,
        "seq_len": 32, "vocab_size": 96, "ffn_dense": 160,
        "ffn_expert": 48, "n_experts": 8, "experts_per_token": 2,
        "experts_held": 4, "first_expert": 2, "routed_scaling": 1.0,
        "rope_theta": 1e6, "rms_eps": 1e-5, "bias_rate": 0.001,
        "batch_size": 8, "learning_rate": 1e-3, "train_steps": 4,
        "steps_per_dispatch": 2, "remat": "dots", "quick_train": False,
        "seed": 5}
TINY_CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 5, "layer_types": PATTERN, "num_dense_layers": 1,
    "conv_L_cache": 3, "max_position_embeddings": 32, "vocab_size": 96,
    "intermediate_size": 160, "moe_intermediate_size": 48,
    "router_experts": 8, "num_experts_per_tok": 2, "num_experts": 4,
    "first_expert": 2, "routed_scaling_factor": 1, "rope_theta": 1e6,
    "norm_eps": 1e-5, "bias_update_rate": 0.001}
RECIPE = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4,
          "warmup_div": 10, "start_factor": 0.1, "end_factor": 0.1}
DIMS = reference.dims_of(TINY_CONFIG)
BIASES = ("attn_sparse_bias", "conv_sparse_bias")


class TinyLfm2(JaxLfm2MoeLM):
    @staticmethod
    def get_knob_config():
        knobs = dict(JaxLfm2MoeLM.get_knob_config())
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
    rng = np.random.default_rng(11)
    for name in BIASES:
        flat[name] = jnp.asarray(
            0.05 * rng.standard_normal(flat[name].shape), jnp.float32)
    model = TinyLfm2(**TINY)
    model.load_parameters(_program_names(
        {k: np.asarray(v) for k, v in flat.items()}))
    win = jnp.asarray(rng.integers(0, 96, size=(2, 33)), jnp.int32)
    return model, flat, win


@pytest.fixture
def float32(monkeypatch):
    """The program's matmul operands and attention in float32: against
    the float32 reference the equations then hold leaf by leaf, and no
    near-tied top-k choice flips."""
    monkeypatch.setattr(lm_moe, "COMPUTE", jnp.float32)


def test_initialiser_and_layout_are_the_reference_s():
    mine = _reference_names(_flat_names(TinyLfm2(**TINY)._init_params()))
    theirs = reference.init_params(5, DIMS)
    assert set(mine) == set(theirs)
    for name in theirs:
        np.testing.assert_array_equal(mine[name], np.asarray(theirs[name]),
                                      err_msg=name)
    # stacked by kind, the deepest stack the one compare.py cuts by:
    # every layer's matrix is a leaf of its own in the comparison
    assert mine["conv_sparse/in"].shape == (3, 64, 192)
    assert mine["attn_sparse/e_gate"].shape == (1, 4, 64, 48)
    assert mine["conv_dense/filter"].shape == (1, 3, 64)
    assert DIMS["layers"] == 3
    leaves = [leaf for leaf, _, _ in compare._cut(theirs, DIMS["layers"])]
    assert "conv_sparse/router[2]" in leaves and "attn_sparse/k" in leaves
    routed = [leaf for leaf, key, _ in compare._cut(theirs, DIMS["layers"])
              if reference.is_routed(key)]
    assert len(routed) == 4 * 4  # 4 sparse layers x (3 stacks + router)


def test_logits_match_the_reference_in_bfloat16(seeded):
    model, flat, win = seeded
    mine = model._forward(model._params, win[:, :-1])
    assert mine.dtype == jnp.float32 and mine.shape == (2, 32, 96)
    # bf16 operands against float32 over 5 blocks and the head, position
    # by position: the median, because in four sparse blocks some
    # near-tied top-k choice flips on every draw, a discrete change of
    # that token's output (the reference's own bf16 mode parts from its
    # float32 by as much) that no tolerance describes
    theirs = np.asarray(reference.forward(flat, win[:, :-1], DIMS))
    gaps = [_rel(a, b) for a, b in zip(np.asarray(mine).reshape(64, 96),
                                       theirs.reshape(64, 96))]
    assert np.median(gaps) < 4e-2, np.sort(gaps)


def test_logits_match_the_reference_in_float32(seeded, float32):
    model, flat, win = seeded
    mine = model._forward(model._params, win[:, :-1])
    assert _rel(mine, reference.forward(flat, win[:, :-1], DIMS)) < 1e-4


def test_loss_and_every_gradient_leaf_match_the_reference(seeded, float32):
    model, flat, win = seeded
    s, remat, mesh = model._forward_spec()
    (loss, (_, counts, state)), grads = jax.value_and_grad(
        lm_lfm2._lfm2_loss, has_aux=True)(
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
    # tokens x k over the four sparse blocks; held = experts 2..5
    assert float(counts[0] + counts[1]) == 4 * 2 * 32 * 2
    total = np.concatenate([np.asarray(ref_counts[name])
                            for name in BIASES])
    assert float(counts[0]) == total[:, 2:6].sum()
    assert float(counts[2]) == total[:, 2:6].max(-1).sum()
    # the bias steps against the load: b + gamma * sign(mean(c) - c)
    c = np.asarray(ref_counts["conv_sparse_bias"])
    want = np.asarray(flat["conv_sparse_bias"]) + 0.001 * np.sign(
        c.mean(-1, keepdims=True) - c)
    np.testing.assert_allclose(np.asarray(state["conv_sparse_bias"]), want,
                               atol=1e-7)


def test_the_four_ranks_shares_add_up_to_the_uncut_layer(float32):
    """One sparse layer, 32 experts top-4, shared by four ranks of 8
    (``first_expert`` 0, 8, 16, 24): the parts the ranks compute sum to
    what the uncut reference layer gives (no shared expert to count
    once); selection and the gates' normalisation run over all 32 in
    every share, so every rank counts the same tokens per expert."""
    rng = np.random.default_rng(4)

    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]),
                           jnp.float32)

    p = {"router": r(64, 32), "e_gate": r(32, 64, 48),
         "e_up": r(32, 64, 48), "e_down": r(32, 48, 64)}
    bias = jnp.asarray(0.05 * rng.standard_normal(32), jnp.float32)
    u = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    dims = dict(DIMS, experts=32, k=4)

    def f32_dot(a, b):
        return jnp.matmul(a, b, precision="highest")

    whole, counts = reference.experts(u, p, bias, dims, f32_dot, first=0,
                                      held=32)
    assert float(counts.sum()) == 2 * 24 * 4
    total = np.zeros(whole.shape, np.float64)
    for first in (0, 8, 16, 24):
        share = {k: (v[first:first + 8] if k.startswith("e_") else v)
                 for k, v in p.items()}
        s = dict(TinyLfm2(**TINY)._dims(), experts=32, k=4, first=first,
                 held=8)
        part, c = lm_lfm2._experts(u, share, bias, s)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts))
        total += np.asarray(part, np.float64)
        # and the reference, given the same share, gives the same part
        theirs, _ = reference.experts(u, share, bias, dims, f32_dot, first,
                                      8)
        assert _rel(part, theirs) < 1e-4
    assert _rel(total, whole) < 1e-4


@pytest.mark.parametrize("pattern,dense", [
    (PATTERN, 1),
    # kinds that come back: conv-sparse runs on either side of attention
    (["conv", "conv", "full_attention", "conv", "full_attention", "conv",
      "conv"], 2)], ids=["cell", "kinds-return"])
def test_the_stack_is_its_blocks_applied_one_after_another(
        pattern, dense, float32):
    """The scanned runs of stacked layers against a plain loop over the
    layers in ``layer_types``' order, each fed its own row of its
    kind's stack."""
    model = TinyLfm2(**dict(TINY, layer_types=pattern,
                            n_layers=len(pattern), n_dense_layers=dense))
    s, remat, mesh = model._forward_spec()
    params = model._init_params()
    rng = np.random.default_rng(2)
    params["state"] = {
        name: jnp.asarray(0.05 * rng.standard_normal(b.shape), jnp.float32)
        for name, b in params["state"].items()}
    ids = jnp.asarray(rng.integers(0, 96, size=(2, 32)), jnp.int32)
    kinds = lm_lfm2.kinds_of(s)
    assert [k for k, _, _ in lm_lfm2.runs_of(s)] == [
        k for i, k in enumerate(kinds) if i == 0 or kinds[i - 1] != k]
    got, got_counts = lm_lfm2._lfm2_hidden(params, ids, s, remat, mesh)
    x, row, counts = params["embed"][ids], {}, {}
    for kind in kinds:
        i = row.get(kind, 0)
        row[kind] = i + 1
        p = jax.tree.map(lambda a: a[i], params["blocks"][kind])
        bias = params["state"][f"{kind}_bias"][i] \
            if kind.endswith("sparse") else None
        x, c = lm_lfm2._block(x, p, bias, kind, s, mesh)
        if c is not None:
            counts.setdefault(f"{kind}_bias", []).append(c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), rtol=1e-4,
                               atol=2e-5)
    assert set(got_counts) == set(counts)
    for name, rows in counts.items():
        np.testing.assert_array_equal(np.asarray(got_counts[name]),
                                      np.asarray(jnp.stack(rows)))


@pytest.fixture(scope="module")
def token_data(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("lfm2_lm")
    return make_synthetic_token_dataset(
        str(tmp), n_train=1 << 12, n_val=1 << 8, vocab_size=96,
        branching=2)


def test_four_optimizer_steps_follow_the_reference(token_data, float32):
    """What the benchmark compares, at tiny size: the logged losses of
    a 4-step trial (two dispatches of two; batch 8, a row a device of
    the tests' dp=8 mesh) and every number ``compare.py`` gives of the
    parameters' change, router biases included, against the reference's
    own run of the trial from the seed."""
    from rafiki_tpu.model.dataset import load_token_dataset
    from rafiki_tpu.model.logger import logger

    train_path, _ = token_data
    logged = []
    logger.set_sink(lambda record: logged.append(record))

    class Float32LM(TinyLfm2):  # its train chunk is traced under float32
        pass

    try:
        model = Float32LM(**TINY)
        before, layers = phases.moe_counts(), phases.lm_layer_counts()
        model.train(train_path)
    finally:
        logger.set_sink(None)
    losses = [r["values"]["loss"] for r in logged
              if "loss" in (r.get("values") or {})]
    ids = load_token_dataset(train_path).ids
    first, final, step_losses = reference.train(
        ids, 5, DIMS, RECIPE, steps=4, batch=8, per_dispatch=2,
        learning_rate=1e-3)
    assert first["embed"].dtype == final["embed"].dtype == np.float32
    numbers = compare.trial_numbers(
        losses, step_losses, 2, _reference_names(model.dump_parameters()),
        final, first, DIMS["layers"], **compare.kinds_of(reference))
    assert numbers["loss_gap"]["value"] < 1e-5, numbers["loss_gap"]
    assert numbers["dparam_gap"]["value"] < 2e-3, numbers["dparam_gap"]
    assert numbers["update_gap"]["value"] < 2e-2, numbers["update_gap"]
    assert numbers["routed_gap"]["leaves"] == 16
    assert numbers["state_gap"]["value"] < 1e-6, numbers["state_gap"]
    mine = _reference_names(model.dump_parameters())
    for name in BIASES:  # moved, by gamma a step
        assert 0 < np.abs(mine[name]).max() <= 4 * 0.001 + 1e-9
    # held + absent = tokens x k over every sparse block of every step
    grew = {k: v - before[k] for k, v in phases.moe_counts().items()}
    assert grew["held"] + grew["absent"] == 4 * 8 * 32 * 2 * 4
    assert 0 < grew["held"] < grew["held"] + grew["absent"]
    # the trial said which pattern it built
    built = {k: v - layers.get(k, 0)
             for k, v in phases.lm_layer_counts().items()}
    assert built == {("conv", "dense"): 1, ("attention", "sparse"): 1,
                     ("conv", "sparse"): 3}
    model.destroy()


def test_the_control_runs_on_this_reference_as_it_stands(tmp_path,
                                                         monkeypatch):
    """``selftest/control_joyai.py`` loads whatever reference the
    configuration names: the float8 control of this one is compared
    with its own float32 trial and held to the workload's limits."""
    monkeypatch.syspath_prepend(BENCH)
    control = _bench_module("selftest", "control_joyai.py")
    config = dict(TINY_CONFIG, reference="lfm2_moe", recipe=RECIPE,
                  data={"generator": "tokens", "n_train": 4096,
                        "branching": 2},
                  knobs={"batch_size": 2, "steps_per_dispatch": 2})

    def workload(**limits):
        return {"job": {"fixed": {"train_steps": 4,
                                  "learning_rate": 1e-3}},
                "limits": limits}

    tight = workload(loss_gap_first=1e-5, routed_gap=1e-2, state_gap=0.5,
                     dparam_gap=0.5)
    assert control.stage(config, tight, 7, "f32", "", str(tmp_path)) is None
    out = control.stage(config, tight, 7, "fp8", "", str(tmp_path))
    assert out["correct"] is False
    assert out["loss_gap_first"] > 1e-5 and 1e-2 < out["routed_gap"] < 2
    fault = control.stage(config, tight, 7, "f32", "half_batch",
                          str(tmp_path))
    assert fault["correct"] is False and fault["loss_gap_first"] > 1e-4
    wide = control.stage(
        config, workload(loss_gap_first=0.9, routed_gap=0.99,
                         state_gap=0.99, dparam_gap=0.99), 7, "fp8", "",
        str(tmp_path))
    assert wide == dict(out, correct=True)


def test_predict_scores_through_the_shared_forward(seeded):
    model, flat, win = seeded
    query = np.asarray(win[0, :20]).tolist()
    (score,) = model.predict([query])
    logits = reference.forward(flat, win[:1, :19], DIMS)
    logp = jax.nn.log_softmax(logits, -1)
    want = float(np.mean([logp[0, i, query[i + 1]] for i in range(19)]))
    assert abs(score - want) < 2e-2 * abs(want)


def test_generation_is_refused_with_one_clear_error(seeded):
    model, _, _ = seeded
    with pytest.raises(NotImplementedError,
                       match="convolution state.*sparse-expert"):
        model.make_generator(page_size=16)


def test_dump_and_load_round_trip_the_nested_tree(seeded):
    model, _, win = seeded
    dumped = model.dump_parameters()
    assert {"embed", "lnf", "blocks/conv_dense/in", "blocks/attn_sparse/k",
            "blocks/conv_sparse/e_gate", "state/attn_sparse_bias",
            "state/conv_sparse_bias"} <= set(dumped)
    assert dumped["blocks/conv_sparse/e_gate"].shape == (3, 4, 64, 48)
    other = TinyLfm2(**TINY)
    other.load_parameters(dumped)
    np.testing.assert_array_equal(
        np.asarray(other._forward(other._params, win[:, :-1])),
        np.asarray(model._forward(model._params, win[:, :-1])))


def test_the_step_count_is_the_benchmark_s(seeded):
    """``chip_util`` is fed the class's own ``_flops_per_step``:
    pinned to ``benchmarks/flops_lfm2.py`` at the expected routing."""
    flops_lfm2 = _bench_module("flops_lfm2.py")
    model, _, _ = seeded
    mine = model._flops_per_step(8)
    assert mine == pytest.approx(flops_lfm2.train_step_flops(
        flops_lfm2.dims(dict(TINY))), rel=1e-12)


_UPLOADED = '''
from rafiki_tpu.model import FixedKnob
from rafiki_tpu.models import JaxLfm2MoeLM


class UploadedLfm2(JaxLfm2MoeLM):
    @staticmethod
    def get_knob_config():
        knobs = dict(JaxLfm2MoeLM.get_knob_config())
        knobs.update({name: FixedKnob(v) for name, v in %r.items()})
        return knobs
'''


def test_a_train_job_trains_it_and_generation_is_refused_at_the_deploy(
        token_data, tmp_path, monkeypatch):
    """Uploaded as a template (the ``layer_types`` knob a list, as the
    benchmark's configuration gives it) and trained by
    ``create_train_job`` like any other class, two congruent trials on
    one compiled step; ``create_inference_job`` with generative serving
    on fails AT the deploy with the one error that names what
    ``lm_generate.py`` lacks."""
    from rafiki_tpu.constants import TaskType, UserType
    from rafiki_tpu.platform import LocalPlatform

    train_path, val_path = token_data
    platform = LocalPlatform(workdir=str(tmp_path / "plat"), http=False,
                             supervise_interval=0)
    try:
        dev = platform.admin.create_user("lfm2@x.c", "pw",
                                         UserType.MODEL_DEVELOPER)
        model = platform.admin.create_model(
            dev["id"], "lfm2-lm", TaskType.LANGUAGE_MODELING,
            "UploadedLfm2",
            model_source=_UPLOADED % dict(TINY, train_steps=2))
        before = phases.cache_counts("step")
        job = platform.admin.create_train_job(
            dev["id"], "lfm2-app", TaskType.LANGUAGE_MODELING,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 2},
            train_path, val_path)
        assert platform.admin.wait_until_train_job_done(job["id"],
                                                        timeout=600)
        trials = platform.meta.get_trials_of_train_job(job["id"])
        assert [t["status"] for t in trials] == ["COMPLETED"] * 2
        after = phases.cache_counts("step")
        assert {k: after.get(k, 0) - before.get(k, 0)
                for k in ("miss", "hit")} == {"miss": 2, "hit": 2}
        stored = platform.params.load(trials[-1]["params_id"])
        assert "state/conv_sparse_bias" in stored \
            and "blocks/attn_sparse/q_norm" in stored
        monkeypatch.setenv("RAFIKI_TPU_SERVING_GENERATE", "1")
        with pytest.raises(ValueError, match="convolution state"):
            platform.admin.create_inference_job(dev["id"], job["id"],
                                                max_models=1)
    finally:
        platform.shutdown()
