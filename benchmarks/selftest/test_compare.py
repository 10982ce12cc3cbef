"""``compare.py`` on trees made here, and the thing itself at a toy
size: the sparse-expert reference's ``bf16`` mode in the program's place
reads ``correct`` true, its ``fp8`` mode false BY ``update_gap`` and
``routed_gap``.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/selftest/test_compare.py -q

``run_selftest.py compare`` runs this file. It lies under the
benchmark's own directory, so the driver's tier-1 command does not
collect it (PERF.md section 7).
"""

from __future__ import annotations

import math
import os
import sys
import tracemalloc

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import compare  # noqa: E402

LAYERS = 3


def is_state(name: str) -> bool:
    return name.endswith("_bias")


def is_routed(name: str) -> bool:
    return name == "w2"


def trees(seed: int = 0, size: int = 40):
    """(initial, reference's final) of a small model: two stacked
    matrices, an unstacked one, a gain and a state leaf."""
    rng = np.random.default_rng(seed)
    shapes = {"w1": (LAYERS, size, size), "w2": (LAYERS, size, 2 * size),
              "embed": (5 * size, size), "lnf": (size,),
              "route_bias": (LAYERS, 16)}
    first = {k: rng.standard_normal(s).astype(np.float32)
             for k, s in shapes.items()}
    final = {k: (v + 1e-2 * rng.standard_normal(v.shape)).astype(np.float32)
             for k, v in first.items()}
    final["route_bias"] = (first["route_bias"] + 1e-3 * rng.integers(
        -4, 5, size=shapes["route_bias"])).astype(np.float32)
    return first, final


def numbers_of(program, final, first):
    return compare.tree_numbers(program, final, first, LAYERS, is_state,
                                is_routed)


def case_identical():
    first, final = trees()
    got = numbers_of({k: v.copy() for k, v in final.items()}, final, first)
    assert set(got) == {"dparam_gap", "update_gap", "routed_gap",
                        "state_gap"} <= set(compare.NUMBERS)
    assert all(n["value"] == 0 for n in got.values()), got
    assert got["update_gap"]["worst"] == 0 == got["state_gap"]["diff"]
    assert got["routed_gap"]["leaves"] == LAYERS
    # a reference that names no state and no routed leaf: the bias is a
    # leaf like any other
    plain = compare.tree_numbers(final, final, first, LAYERS)
    assert set(plain) == {"dparam_gap", "update_gap"}


def case_leaf_unmoved():
    first, final = trees()
    program = {k: v.copy() for k, v in final.items()}
    program["w2"][1] = first["w2"][1]
    got = numbers_of(program, final, first)
    assert got["dparam_gap"] == {"value": 1.0, "leaf": "w2[1]"}
    assert got["update_gap"]["worst"] == 1.0
    assert got["update_gap"]["leaf"] == "w2[1]"
    assert got["update_gap"]["value"] == 0.0  # the median leaf is sound
    assert got["routed_gap"]["value"] == 0.0  # and the median routed one
    assert got["state_gap"]["value"] == 0.0
    program["w2"][2] = first["w2"][2]  # two of the three routed leaves
    got = numbers_of(program, final, first)
    assert got["routed_gap"]["value"] == 1.0
    assert got["update_gap"]["value"] == 0.0


def case_leaf_moved_double():
    first, final = trees()
    program = {k: v.astype(np.float64) for k, v in final.items()}
    program["embed"] = 2.0 * final["embed"].astype(np.float64) \
        - first["embed"]
    got = numbers_of(program, final, first)
    assert got["dparam_gap"]["leaf"] == "embed"
    assert got["dparam_gap"]["value"] == pytest.approx(1.0, rel=1e-9)
    assert got["update_gap"]["worst"] == pytest.approx(1.0, rel=1e-9)
    assert got["update_gap"]["leaf"] == "embed"


def case_state_leaf():
    first, final = trees()
    program = {k: v.copy() for k, v in final.items()}
    program["route_bias"] = first["route_bias"].copy()  # never updated
    got = numbers_of(program, final, first)
    assert got["state_gap"] == {"value": 1.0, "leaf": "route_bias[0]",
                                "diff": 1.0}
    assert got["dparam_gap"]["value"] == 0 == got["update_gap"]["worst"]
    # half of one layer's steps taken the other way: the two 1-norms of
    # the change are equal, and ``diff`` shows it
    program["route_bias"] = final["route_bias"].copy()
    step = final["route_bias"][2] - first["route_bias"][2]
    program["route_bias"][2, :8] -= 2 * step[:8]
    got = numbers_of(program, final, first)
    assert got["state_gap"]["value"] == pytest.approx(0, abs=1e-6)
    assert got["state_gap"]["diff"] == pytest.approx(
        2 * np.abs(step[:8]).sum() / np.abs(step).sum(), rel=1e-5)
    # one layer's steps taken at half their size: by the 1-norm
    program["route_bias"] = final["route_bias"].copy()
    program["route_bias"][2] -= 0.5 * step
    got = numbers_of(program, final, first)
    assert got["state_gap"]["leaf"] == "route_bias[2]"
    assert got["state_gap"]["value"] == pytest.approx(0.5, rel=1e-4)
    assert got["dparam_gap"]["value"] == 0 == got["update_gap"]["value"]
    # the same leaf under a reference that names no state: in both norms
    plain = compare.tree_numbers(program, final, first, LAYERS)
    assert plain["update_gap"]["leaf"] == "route_bias[2]"
    assert plain["update_gap"]["worst"] == pytest.approx(0.5, rel=1e-4)
    assert "state_gap" not in plain and "routed_gap" not in plain


def case_without_a_limit():
    numbers = {"loss_gap": {"value": 1e-5},
               "dparam_gap": {"value": 1e-4, "leaf": "w1[0]"},
               "update_gap": {"value": 0.9, "worst": 2.0, "leaf": "w2[1]"},
               "bad_trials": {"value": 0}}
    compared, correct = compare.judge(
        numbers, {"loss_gap": 1e-4, "dparam_gap": 1e-3})
    assert correct is True
    assert compared["update_gap"] == {"value": 0.9, "limit": None,
                                      "worst": 2.0, "leaf": "w2[1]"}
    assert compared["bad_trials"] == {"value": 0, "limit": 0}
    assert list(compared) == list(numbers)
    # the same numbers once the cell limits it; a limit at the reading
    assert compare.judge(numbers, {"update_gap": 0.5})[1] is False
    assert compare.judge(numbers, {"update_gap": 0.9})[1] is True
    # bad_trials is held to 0 whatever the file says
    assert compare.judge(dict(numbers, bad_trials={"value": 1}),
                         {"bad_trials": 5})[1] is False
    # a limit on a number that was not read, and a reading that is no
    # number, fail closed
    assert compare.judge(numbers, {"state_gap": 0.5})[1] is False
    assert compare.judge(dict(numbers, loss_gap={"value": math.nan}),
                         {"loss_gap": 1.0})[1] is False
    # None says "shown, not judged", as leaving the number out does
    compared, correct = compare.judge(
        numbers, {"update_gap": None, "loss_gap": 1e-4})
    assert correct is True and compared["update_gap"]["limit"] is None
    # one accepted file keeps its newer limits beside the pinned ones
    assert compare.limits_of({"limits": {"a": 1, "b": 2},
                              "limits_more": {"b": 3, "c": 4}}) \
        == {"a": 1, "b": 3, "c": 4}
    assert compare.limits_of({"limits": {"a": 1}}) == {"a": 1}


def case_broken_trees():
    first, final = trees()
    program = {k: v.copy() for k, v in final.items()}
    program["w1"][2, 0, 0] = np.nan
    got = numbers_of(program, final, first)
    assert all(n["value"] == math.inf for n in got.values()), got
    assert all(n["leaf"] == "w1[2]" for name, n in got.items()
               if name != "routed_gap"), got
    assert set(got) == {"dparam_gap", "update_gap", "routed_gap",
                        "state_gap"}
    assert compare.judge(got, {"update_gap": 10.0})[1] is False
    renamed = {("w3" if k == "w2" else k): v for k, v in final.items()}
    got = numbers_of(renamed, final, first)
    assert all(n["value"] == math.inf for n in got.values())
    assert got["dparam_gap"]["leaf"] == "leaf names differ"
    reshaped = dict(final, lnf=final["lnf"][:-1])
    assert numbers_of(reshaped, final, first)["update_gap"] == {
        "value": math.inf, "worst": math.inf, "leaf": "lnf"}
    assert compare.dparam_gap(reshaped, final, first, LAYERS) \
        == (math.inf, "lnf")


def case_float32_no_whole_tree_copy():
    """float32 trees give the float64 trees' numbers, and the pass
    holds a few leaves in float64, never a tree: 16 leaves of 250,000
    make a float64 tree 32 MB, three of them 96."""
    rng = np.random.default_rng(1)
    first = {f"m{i:02d}": rng.standard_normal((LAYERS + 1, 62500)).astype(
        np.float32) for i in range(16)}
    final = {k: (v + 1e-2 * rng.standard_normal(v.shape)).astype(np.float32)
             for k, v in first.items()}
    program = {k: (v + 1e-3 * rng.standard_normal(v.shape)).astype(
        np.float32) for k, v in final.items()}
    tracemalloc.start()
    got = compare.tree_numbers(program, final, first, LAYERS)
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert peak < 16e6, peak  # half of one float64 tree
    wide = compare.tree_numbers(
        *({k: v.astype(np.float64) for k, v in t.items()}
          for t in (program, final, first)), LAYERS)
    for name in got:
        assert got[name]["leaf"] == wide[name]["leaf"]
        assert got[name]["value"] == pytest.approx(wide[name]["value"],
                                                   rel=1e-12)
    assert 0.05 < got["update_gap"]["value"] < 0.2


CASES = [case_identical, case_leaf_unmoved, case_leaf_moved_double,
         case_state_leaf, case_without_a_limit, case_broken_trees,
         case_float32_no_whole_tree_copy]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_compare(case):
    case()


#: A sixteenth of ``joyai-llm-flash-L5-E8``'s widths: one dense and two
#: sparse layers, a 64-wide router with 8 a token and 8 held, the
#: multi-token module.
TOY = {
    "hidden_size": 128, "num_attention_heads": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "max_position_embeddings": 128,
    "vocab_size": 512, "q_lora_rank": 96, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 448, "moe_intermediate_size": 48,
    "router_experts": 64, "num_experts_per_tok": 8, "n_routed_experts": 8,
    "first_expert": 8, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "rope_theta": 32e6,
    "rms_norm_eps": 1e-6, "num_nextn_predict_layers": 1,
    "mtp_loss_weight": 0.3, "bias_update_rate": 0.001}
RECIPE = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 1e-4,
          "warmup_div": 10, "start_factor": 0.1, "end_factor": 0.1}
#: loss_gap and dparam_gap as wide as the cell's own; update_gap and
#: routed_gap the geometric mean of the toy's largest bf16 and smallest
#: fp8 reading (0.077 and 0.394; 0.226 and 0.678: my CPU runs, PR 35,
#: seeds 11 and 12).
TOY_LIMITS = {"loss_gap": 1e-1, "loss_gap_first": 1e-1, "dparam_gap": 1e-1,
              "update_gap": 0.17, "routed_gap": 0.39, "state_gap": 0.5}


def test_a_precision_step_fails_by_the_update():
    """Two seeds, 8 steps of one 128-token row, 4 a dispatch: the
    reference with bfloat16 operands (what the configuration states)
    reads ``correct`` true; with float8 operands false, by
    ``update_gap`` and ``routed_gap`` and by nothing else, 3 and 2.5
    times apart or more."""
    from harness import load_module

    reference = load_module("reference", "joyai_flash")
    data = load_module("data", "tokens")
    dims = reference.dims_of(TOY)
    sound, control = [], []
    for seed in (11, 12):
        ids, _ = data.streams(seed, vocab_size=dims["v"], n_train=1 << 14,
                              n_val=8, branching=4)

        def trial(mode):
            return reference.train(
                ids, seed, dims, RECIPE, steps=8, batch=1, per_dispatch=4,
                learning_rate=2.2e-4, mode=mode, host_dtype=np.float32)

        first, final, losses = trial("f32")
        assert final["head"].dtype == np.float32
        for mode, into in (("bf16", sound), ("fp8", control)):
            _, theirs, their_losses = trial(mode)
            numbers = compare.trial_numbers(
                compare.chunk_means(their_losses, 4), losses, 4, theirs,
                final, first, dims["layers"],
                **compare.kinds_of(reference))
            compared, correct = compare.judge(numbers, TOY_LIMITS)
            print(seed, mode, correct, compared)
            assert correct is (mode == "bf16"), (seed, mode, compared)
            over = [name for name, pair in compared.items()
                    if pair["value"] > pair["limit"]]
            assert over == ([] if correct
                            else ["update_gap", "routed_gap"]), over
            into.append([numbers[name]["value"]
                         for name in ("update_gap", "routed_gap")])
    apart = np.min(control, axis=0) / np.max(sound, axis=0)
    assert apart[0] >= 3 and apart[1] >= 2.5, (sound, control)
