"""The benchmark's files for the ``joyai-flash-final`` cell: the work
counts (``benchmarks/flops_moe.py``) against a hand count and against
the model's own ``_flops_per_step``; the new readers on hand-made
records; the configuration, workload and ``BENCHMARK.json`` entries
under the selftest's own schema assertions."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "joyai-flash-final"
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, imported as ``run.py`` imports them."""
    sys.path.insert(0, BENCH)
    try:
        import flops_moe
        import harness
        yield {"flops_moe": flops_moe,
               "reader": lambda name: harness.load_module("metrics", name)}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def cell_knobs():
    with open(os.path.join(BENCH, "configs",
                           "joyai-llm-flash-L5-E8.json")) as f:
        config = json.load(f)
    knobs = {knob: config[key] for knob, key in config["knob_of"].items()}
    knobs.update(config["knobs"])
    return dict(knobs, train_steps=32, learning_rate=2.2e-4), config


def test_step_flops_against_a_hand_count(bench, cell_knobs):
    """JoyAI-LLM-Flash's published widths, by hand (ISSUE 29's
    arithmetic): MLA 26.35 M a layer, dense feed-forward 44.04 M,
    router 0.52 M, shared and routed expert 4.72 M each, a head of
    33.1 M; 8,192 tokens; 2,048 assignments held a step in expectation
    over the four sparse layers, 2,047.75 in the multi-token module."""
    f = bench["flops_moe"]
    s = f.dims(cell_knobs[0])
    mla = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
           + 32 * 128 * 2048)
    assert f.attention_params(s) == mla == 26_345_472
    expert = 3 * 2048 * 768
    assert f.expert_params(s) == expert == 4_718_592
    sparse = mla + 2048 * 256 + expert
    head = 16160 * 2048
    t = 8192
    held = (4 * t + (t - 1)) * 8 * 8 / 256
    assert f.expected_held_per_step(s) == held
    by_hand = (
        6 * t * (mla + 3 * 2048 * 7168 + 4 * sparse + head)
        + 6 * (t - 1) * (2 * 2048 * 2048 + sparse + head)
        + 6 * expert * held
        + 3 * 32 * (192 + 128) * (5 * t * t + (t - 1) ** 2))
    assert abs(f.train_step_flops(s) - by_hand) <= 1e-12 * by_hand
    assert 27.4e12 < by_hand < 27.7e12
    # the routed experts follow the assignments REALLY held
    assert f.train_step_flops(s, 2 * held) - f.train_step_flops(s) \
        == pytest.approx(6 * expert * held)
    # useful attention: 192 lanes for q k^T, 128 for p v, causal half
    fwd, bound = f.attention_fwd_least(s, PEAKS)
    assert bound == "compute"
    assert fwd == pytest.approx(32 * t * t * (192 + 128) / 197e12)
    bwd, _ = f.attention_bwd_least(s, PEAKS)
    assert bwd == pytest.approx(2 * fwd)
    # 256 tokens an expert: the products are bound by reading weights
    _, bound = f.experts_least(s, held, PEAKS)
    assert bound == "memory"


def test_chip_util_counts_the_sparse_step(bench, cell_knobs):
    """``MfuMeter`` (``chip_util``, ``rafiki_tpu_train_mfu_ratio``) is
    fed the class's own ``_flops_per_step``: the sparse count, pinned
    to the benchmark's, not the dense block's 6 N tokens."""
    from rafiki_tpu.models import JaxLatentMoELM, JaxTransformerLM

    knobs, _ = cell_knobs
    f = bench["flops_moe"]
    model = JaxLatentMoELM(**knobs)
    mine = model._flops_per_step(1)
    assert mine == pytest.approx(f.train_step_flops(f.dims(knobs)),
                                 rel=1e-12)
    assert mine == pytest.approx(
        f.train_step_flops(dict(f.dims(knobs), batch=2)) / 2, rel=1e-12)
    dense = JaxTransformerLM(d_model=2048, n_layers=5, seq_len=8192,
                             vocab_size=16160)._flops_per_step(1)
    assert abs(dense - mine) > 0.2 * mine


def _op(seconds, n, short):
    return {"seconds": seconds, "n": n, "short": short}


def _kernel(name, n_operands, n_results, named=True):
    shape = "bf16[32,8192,256]{2,1,0:T(8,128)(2,1)}"
    results = ", ".join([shape] * n_results) if n_operands == 6 \
        else f"{shape}, f32[32,8192,8]{{2,1,0}}"
    operands = ", ".join(f"{shape} %p{i}" for i in range(min(
        n_operands, 4))) + "".join(
            f", f32[32,1,8192]{{2,1,0}} %s{i}"
            for i in range(n_operands - 4))
    meta = f', frontend_attributes={{kernel_metadata={{\n"kernel":' \
           f'"{name}"\n}}}}' if named else ""
    return (f"%checkpoint.{n_operands}{n_results} = ({results}) "
            f"custom-call({operands}), custom_call_target="
            f'"tpu_custom_call"{meta}')


@pytest.fixture()
def traced(cell_knobs):
    """A hand-made run record: a traced slice of 16 steps (two train
    chunks of 8) with the ops the new readers look for."""
    knobs, _ = cell_knobs
    ops = {
        _kernel("flash_fwd", 3, 2): _op(0.96, 192, "checkpoint.32 "
                                        "custom-call bf16[32,8192,256]"),
        _kernel("flash_dq", 6, 1): _op(0.96, 96, "checkpoint.61 "
                                       "custom-call bf16[32,8192,256]"),
        _kernel("flash_dkv", 6, 2): _op(1.92, 96, "checkpoint.62 "
                                        "custom-call bf16[32,8192,256]"),
        # an expert loop, two products inside it, routing outside
        "%while.1 = (s32[], f32[8192,2048]{1,0}, s32[65664]{0}, "
        "bf16[8,2048,768]{2,1,0}) while(%tuple.1), condition=%c, body=%b":
            _op(0.40, 160, "while.1 while s32[]"),
        "%fusion.7 = f32[128,768]{1,0} fusion(bf16[128,2048]{1,0} %x, "
        "bf16[8,2048,768]{2,1,0} %w, s32[] %e), kind=kOutput":
            _op(0.10, 3200, "fusion.7 fusion f32[128,768]"),
        "%fusion.9 = f32[8,2048,768]{2,1,0} fusion(f32[8,2048,768]{2,1,0} "
        "%dw, bf16[128,2048]{1,0} %x, bf16[128,768]{1,0} %dh, s32[] %e)":
            _op(0.06, 1600, "fusion.9 fusion f32[8,2048,768]"),
        "%sort.3 = (s32[65536]{0}, s32[65536]{0}, f32[65536]{0}) sort("
        "s32[65536]{0} %k, s32[65536]{0} %f, f32[65536]{0} %g)":
            _op(0.02, 160, "sort.3 sort s32[65536]"),
        "%fusion.11 = f32[8192,256]{1,0} fusion(f32[8192,2048]{1,0} %u, "
        "f32[2048,256]{1,0} %r)": _op(0.04, 160, "fusion.11 fusion "
                                      "f32[8192,256]"),
        # inside a loop: counted by the loop, not again as routing
        "%dynamic-slice.5 = s32[128]{0} dynamic-slice(s32[65664]{0} %t, "
        "s32[] %i)": _op(0.01, 3200, "dynamic-slice.5 dynamic-slice "
                         "s32[128]"),
        "%fusion.1 = bf16[1,8192,7168]{2,1,0} fusion(bf16[1,8192,2048] %x)":
            _op(1.0, 32, "fusion.1 fusion bf16[1,8192,7168]"),
    }
    return {"trace": {"ops": ops, "window_s": 10.0, "busy_s": 9.5,
                      "programs": {"jit_train_chunk": [4.8, 4.8]}},
            "knobs": knobs, "peaks": PEAKS, "chips": 1}


def test_new_readers_on_a_hand_made_trace(bench, traced, monkeypatch):
    f = bench["flops_moe"]
    s = f.dims(traced["knobs"])
    reader = bench["reader"]
    kernels = reader("mla_attn_fwd_roofline").kernels(traced)
    assert {k: v["n"] for k, v in kernels.items()} == {
        "flash_fwd": 192, "flash_dq": 96, "flash_dkv": 96}
    fwd_least = 32 * 8192 ** 2 * 320 / 197e12
    assert reader("mla_attn_fwd_roofline").read(traced) == pytest.approx(
        100 * fwd_least / (0.96 / 192))
    assert reader("mla_attn_bwd_roofline").read(traced) == pytest.approx(
        100 * 2 * fwd_least / (0.96 / 96 + 1.92 / 96))
    # the names gone (an older runtime): told by signature instead
    bare = dict(traced, trace=dict(traced["trace"], ops={
        _kernel("x", 3, 2, named=False): _op(0.96, 192, "a custom-call"),
        _kernel("x", 6, 1, named=False): _op(0.96, 96, "b custom-call"),
        _kernel("x", 6, 2, named=False): _op(1.92, 96, "c custom-call")}))
    assert reader("mla_attn_fwd_roofline").read(bare) == pytest.approx(
        100 * fwd_least / (0.96 / 192))
    parts = reader("moe_expert_roofline").split(traced)
    assert parts == pytest.approx(
        {"loops": 0.40, "products": 0.16, "outside": 0.06})
    # the counters are the program's own, cumulative over the run
    from rafiki_tpu.observe import phases

    monkeypatch.setattr(phases, "moe_counts", lambda: {
        "held": 10240 * 40, "absent": 317430 * 40, "busiest": 1600 * 40})
    monkeypatch.setattr(phases, "phase_totals", lambda: {
        "step_wait": {"count": 5, "sum": 1.0}})
    assert reader("moe_load_imbalance").read(traced) == pytest.approx(
        1600 * 8 / 10240)
    held = 10240.0  # a step: 40 steps dispatched
    least, _ = f.experts_least(s, held, PEAKS)
    assert reader("moe_expert_roofline").read(traced) == pytest.approx(
        100 * least / (0.16 / 16))
    assert reader("moe_route_ms").read(traced) == pytest.approx(
        1e3 * (0.40 - 0.16 + 0.06) / 16)
    assert reader("moe_step_mfu").read(traced) == pytest.approx(
        100 * 16 * f.train_step_flops(s, held) / (10.0 * 197e12))
    # nothing to read: no number, no error
    empty = dict(traced, trace=dict(traced["trace"], ops={}, programs={}))
    dense = dict(traced, knobs={"steps_per_dispatch": 8})
    for name in ("moe_step_mfu", "mla_attn_fwd_roofline",
                 "mla_attn_bwd_roofline", "moe_expert_roofline",
                 "moe_route_ms"):
        assert reader(name).read(empty) is None, name
        assert reader(name).read(dict(traced, trace=None)) is None, name
    for name in ("mla_attn_fwd_roofline", "mla_attn_bwd_roofline",
                 "moe_expert_roofline", "moe_route_ms",
                 "moe_load_imbalance"):
        assert reader(name).read(dense) is None, name
    monkeypatch.setattr(phases, "moe_counts", lambda: {
        "held": 0, "absent": 0, "busiest": 0})
    assert reader("moe_load_imbalance").read(traced) is None
    assert reader("moe_step_mfu").read(traced) is None


def test_benchmark_json_config_and_workload_pass_the_schema(cell_knobs):
    """``selftest/run_selftest.py:check_schema``'s own assertions, and
    what ISSUE 29 asks of the new entries."""
    spec = importlib.util.spec_from_file_location(
        "bench_selftest", os.path.join(BENCH, "selftest",
                                       "run_selftest.py"))
    selftest = importlib.util.module_from_spec(spec)
    keep = list(sys.path)
    try:
        spec.loader.exec_module(selftest)
        selftest.check_schema()
    finally:
        sys.path[:] = keep
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [c for c in bench["workloads"] if c["name"] == CELL]
    assert cell == dict(cell, config="joyai-llm-flash-L5-E8",
                        traffic="final", chips=1)
    # by membership and order, so that a later cell and its metrics may
    # follow: after the two dense cells, in the throughput metric too
    names = [c["name"] for c in bench["workloads"]]
    assert names[:3] == ["lm14-final", "lm14-search", CELL]
    (tph,) = [m for m in bench["end_to_end"]
              if m["name"] == "trials_per_hour"]
    assert tph["workloads"][:2] == ["lm14-final", CELL] \
        and tph["bound"] == 0.06
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "moe_step_mfu", "mla_attn_fwd_roofline", "mla_attn_bwd_roofline",
        "moe_expert_roofline", "moe_route_ms", "moe_load_imbalance",
        "moe.step_ms", "moe.device_idle", "moe.trial_nonstep_s",
        "moe.eval_ms", "moe.dump_ms", "moe.persist_ms",
        "moe.compile_s_per_trial", "moe.propose_ms", "moe.handover_wait_ms",
        "moe.train_host_ms", "moe.trial_unattributed_ms"]
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + 17] == mine  # one block
    assert all(CELL not in m["workloads"]
               for m in bench["per_layer"][:first])
    assert all(m["moves"] == "trials_per_hour" for m in mine)
    _, config = cell_knobs
    # every width as published; the router 256 wide with 8 a token
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
              "num_experts_per_tok", "n_shared_experts")
    assert all(config[k] == config["published"][k] for k in widths)
    assert config["router_experts"] == config["published"][
        "n_routed_experts"] == 256 and config["n_routed_experts"] == 8
    assert sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"])
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        workload = json.load(f)
    assert workload["job"]["fixed"] == {"train_steps": 32,
                                        "learning_rate": 2.2e-4}
    assert set(workload["limits"]) == {"loss_gap", "dparam_gap"}
