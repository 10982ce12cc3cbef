"""The benchmark's files for the ``lfm2-moe-final`` cell: the work counts
(``benchmarks/flops_lfm2.py``) against a hand count and against the
model's own ``_flops_per_step``; the new readers on a hand-made trace;
the configuration, workload and ``BENCHMARK.json`` entries; the whole
command at a toy size on the CPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "lfm2-moe-final"
CONFIG = "lfm2-8b-a1b-L5-E8"
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("lfm2_step_mfu", "lfm2_conv_ms", "lfm2_route_ms",
           "lfm2_load_imbalance", "lfm2_attn_fwd_roofline",
           "lfm2_attn_bwd_roofline", "lfm2_expert_roofline")
LIFECYCLE = ("step_ms", "device_idle", "trial_nonstep_s", "eval_ms",
             "dump_ms", "persist_ms", "compile_s_per_trial", "propose_ms",
             "handover_wait_ms", "train_host_ms", "trial_unattributed_ms")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, imported as ``run.py`` imports them."""
    sys.path.insert(0, BENCH)
    try:
        import flops_lfm2
        import harness
        yield {"flops": flops_lfm2,
               "reader": lambda name: harness.load_module("metrics", name)}
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def cell(bench):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    knobs = dict(bench["flops"].knobs_of(config), train_steps=32,
                 learning_rate=2.2e-4)
    return knobs, config


def test_step_flops_against_a_hand_count(bench, cell):
    """LFM2-8B-A1B's published widths, by hand (ISSUE 36's arithmetic):
    a short convolution 2048 x 6144 + 3 x 2048 + 2048 x 2048 = 16.8 M,
    attention 4.19 + 2 x 1.05 + 4.19 = 10.5 M, the dense feed-forward
    44.0 M, a router 65.5 k, an expert 11.0 M, a tied head of 33.6 M;
    8,192 tokens; 4 x 8,192 assignments held a step in expectation."""
    f = bench["flops"]
    s = f.dims(cell[0])
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert f.conv_params(s) == conv == 16_783_360
    attn = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048
    assert f.attention_params(s) == attn == 10_485_760
    expert = 3 * 2048 * 1792
    assert f.expert_params(s) == expert == 11_010_048
    t = 8192
    held = 4 * t * 4 * 8 / 32
    assert f.expected_held_per_step(s) == held == 4 * t
    per_token = (4 * conv + attn + 3 * 2048 * 7168 + 4 * 2048 * 32
                 + 16384 * 2048)
    by_hand = (6 * t * per_token + 6 * expert * held
               + 3 * 32 * 2 * 64 * t * t)
    assert abs(f.train_step_flops(s) - by_hand) <= 1e-12 * by_hand
    assert 10.5e12 < by_hand < 10.8e12
    # the routed experts follow the assignments REALLY held
    assert f.train_step_flops(s, 2 * held) - f.train_step_flops(s) \
        == pytest.approx(6 * expert * held)
    # useful attention: the heads' own 64 lanes, 32 query heads, the
    # causal half; k and v cross the memory once a key-value head
    fwd, bound = f.attention_fwd_least(s, PEAKS)
    assert bound == "compute"
    assert fwd == pytest.approx(2 * 32 * t * t * 64 / 197e12)
    bwd, _ = f.attention_bwd_least(s, PEAKS)
    assert bwd == pytest.approx(2 * fwd)
    memory = f.least_seconds(0.0, f._io_bytes(s, 2, 2), PEAKS)[0]
    assert memory == pytest.approx(2 * t * 64 * 2 * (32 + 8) / 819e9)
    # 1,024 tokens an expert: the products are bound by the MXU
    least, bound = f.experts_least(s, held, PEAKS)
    assert bound == "compute"
    assert least == pytest.approx(6 * expert * held / 197e12)


def test_chip_util_counts_the_hybrid_step(bench, cell):
    """``MfuMeter`` (``chip_util``) is fed the class's own
    ``_flops_per_step``, pinned to the benchmark's count; and the
    benchmark's own self-check finds the configuration."""
    from rafiki_tpu.models import JaxLfm2MoeLM

    knobs, _ = cell
    f = bench["flops"]
    mine = JaxLfm2MoeLM(**knobs)._flops_per_step(1)
    assert mine == pytest.approx(f.train_step_flops(f.dims(knobs)),
                                 rel=1e-12)
    assert mine == pytest.approx(
        f.train_step_flops(dict(f.dims(knobs), batch=2)) / 2, rel=1e-12)
    assert f._self_check() == 0


def _op(seconds, n, short):
    return {"seconds": seconds, "n": n, "short": short}


Q, KV = "bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}", \
    "bf16[8,8192,128]{2,1,0:T(8,128)(2,1)}"
ROW = "f32[32,1,8192]{2,1,0}"


def _kernel(name, named=True, q=Q, kv=KV):
    if name == "flash_fwd":
        results = f"({q}, f32[32,8192,8]{{2,1,0}})"
        operands = f"{q} %q, {kv} %k, {kv} %v"
    else:
        results = q if name == "flash_dq" else f"({kv}, {kv})"
        operands = (f"{kv} %k, {kv} %v, {q} %q, {q} %do, {ROW} %lse, "
                    f"{ROW} %delta")
    meta = f', frontend_attributes={{kernel_metadata={{\n"kernel":' \
           f'"{name}"\n}}}}' if named else ""
    return (f"%gqa_attention.{len(name)}{int(named)}{len(q)} = {results} "
            f"custom-call({operands}), custom_call_target="
            f'"tpu_custom_call"{meta}')


@pytest.fixture()
def traced(cell):
    """A hand-made run record: a traced slice of 16 steps (two train
    chunks of 8) with the ops the new readers look for."""
    knobs, _ = cell
    ops = {
        _kernel("flash_fwd"): _op(0.32, 64, "gqa_attention.32 custom-call"),
        _kernel("flash_dq"): _op(0.16, 16, "gqa_attention.34 custom-call"),
        _kernel("flash_dkv"): _op(0.24, 16, "gqa_attention.35 custom-call"),
        # another model's equal-head call: not this cell's kernels
        _kernel("flash_fwd", kv=Q): _op(9.0, 9, "checkpoint.1 custom-call"),
        # an expert loop, two products inside it, routing outside
        "%while.1 = (s32[], f32[8192,2048]{1,0}, s32[65664]{0}, "
        "bf16[8,2048,1792]{2,1,0}) while(%tuple.1), condition=%c, body=%b":
            _op(0.80, 128, "while.1 while s32[]"),
        "%fusion.7 = f32[128,1792]{1,0} fusion(bf16[128,2048]{1,0} %x, "
        "bf16[8,2048,1792]{2,1,0} %w, s32[] %e), kind=kOutput":
            _op(0.30, 8192, "fusion.7 fusion f32[128,1792]"),
        "%fusion.9 = f32[8,1792,2048]{2,1,0} fusion(f32[8,1792,2048]{2,1,0}"
        " %dw, bf16[128,1792]{1,0} %h, bf16[128,2048]{1,0} %dy, s32[] %e)":
            _op(0.20, 4096, "fusion.9 fusion f32[8,1792,2048]"),
        "%sort.3 = (s32[65536]{0}, s32[65536]{0}, f32[65536]{0}) sort("
        "s32[65536]{0} %k, s32[65536]{0} %f, f32[65536]{0} %g)":
            _op(0.04, 128, "sort.3 sort s32[65536]"),
        "%fusion.11 = f32[8192,32]{1,0} fusion(f32[8192,2048]{1,0} %u, "
        "f32[2048,32]{1,0} %r)": _op(0.02, 128, "fusion.11 fusion "
                                     "f32[8192,32]"),
        # the convolution: the gates over (T, 3 d), forward and backward
        "%fusion.21 = bf16[1,8192,2048]{2,1,0} fusion(bf16[1,8192,6144]"
        "{2,1,0} %bcu, f32[3,2048]{1,0} %w), kind=kLoop":
            _op(0.05, 128, "fusion.21 fusion bf16[1,8192,2048]"),
        "%fusion.22 = bf16[8192,6144]{1,0} fusion(bf16[1,8192,2048]{2,1,0}"
        " %dv, bf16[1,8192,6144]{2,1,0} %bcu, f32[3,2048]{1,0} %w), "
        "kind=kLoop": _op(0.07, 64, "fusion.22 fusion bf16[8192,6144]"),
        # the input projection's products name its weight: not counted
        "%fusion.23 = bf16[8192,6144]{1,0} fusion(bf16[8192,2048]{1,0} %z,"
        " bf16[2048,6144]{1,0} %w), kind=kOutput":
            _op(0.60, 64, "fusion.23 fusion bf16[8192,6144]"),
        "%fusion.24 = f32[3,2048,6144]{2,1,0} fusion(bf16[8192,6144]{1,0} "
        "%g, bf16[8192,2048]{1,0} %z, f32[3,2048,6144]{2,1,0} %acc)":
            _op(0.50, 48, "fusion.24 fusion f32[3,2048,6144]"),
        "%fusion.1 = bf16[1,8192,7168]{2,1,0} fusion(bf16[1,8192,2048] %x)":
            _op(1.0, 32, "fusion.1 fusion bf16[1,8192,7168]"),
    }
    return {"trace": {"ops": ops, "window_s": 5.0, "busy_s": 4.8,
                      "programs": {"jit_train_chunk": [1.2, 1.2]}},
            "knobs": knobs, "peaks": PEAKS, "chips": 1}


def test_new_readers_on_a_hand_made_trace(bench, traced, monkeypatch):
    f = bench["flops"]
    s = f.dims(traced["knobs"])
    reader = bench["reader"]
    kernels = reader("lfm2_attn_fwd_roofline").kernels(traced)
    assert {k: v["n"] for k, v in kernels.items()} == {
        "flash_fwd": 64, "flash_dq": 16, "flash_dkv": 16}
    fwd_least = 2 * 32 * 8192 ** 2 * 64 / 197e12
    assert reader("lfm2_attn_fwd_roofline").read(traced) == pytest.approx(
        100 * fwd_least / (0.32 / 64))
    assert reader("lfm2_attn_bwd_roofline").read(traced) == pytest.approx(
        100 * 2 * fwd_least / (0.16 / 16 + 0.24 / 16))
    # the names gone (an older runtime): told by signature instead
    bare = dict(traced, trace=dict(traced["trace"], ops={
        _kernel("flash_fwd", named=False): _op(0.32, 64, "a custom-call"),
        _kernel("flash_dq", named=False): _op(0.16, 16, "b custom-call"),
        _kernel("flash_dkv", named=False): _op(0.24, 16, "c custom-call")}))
    assert reader("lfm2_attn_bwd_roofline").read(bare) == pytest.approx(
        100 * 2 * fwd_least / (0.16 / 16 + 0.24 / 16))
    parts = reader("lfm2_expert_roofline").split(traced)
    assert parts == pytest.approx(
        {"loops": 0.80, "products": 0.50, "outside": 0.06})
    assert reader("lfm2_conv_ms").seconds(traced) == pytest.approx(0.12)
    assert reader("lfm2_conv_ms").read(traced) == pytest.approx(
        1e3 * 0.12 / 16)
    # the counters are the program's own, cumulative over the run
    from rafiki_tpu.observe import phases

    monkeypatch.setattr(phases, "moe_counts", lambda: {
        "held": 30000 * 40, "absent": 101072 * 40, "busiest": 12000 * 40})
    monkeypatch.setattr(phases, "phase_totals", lambda: {
        "step_wait": {"count": 5, "sum": 1.0}})
    assert reader("lfm2_load_imbalance").read(traced) == pytest.approx(
        12000 * 8 / 30000)
    held = 30000.0  # a step: 40 steps dispatched
    least, bound = f.experts_least(s, held, PEAKS)
    assert bound == "compute"
    assert reader("lfm2_expert_roofline").read(traced) == pytest.approx(
        100 * least / (0.50 / 16))
    assert reader("lfm2_route_ms").read(traced) == pytest.approx(
        1e3 * (0.80 - 0.50 + 0.06) / 16)
    assert reader("lfm2_step_mfu").read(traced) == pytest.approx(
        100 * 16 * f.train_step_flops(s, held) / (5.0 * 197e12))
    for name in READERS:  # shares of a peak stay shares
        assert 0 < reader(name).read(traced) < 100, name
    # nothing to read: no number, no error; another class's run: none
    empty = dict(traced, trace=dict(traced["trace"], ops={}, programs={}))
    other = dict(traced, knobs={"steps_per_dispatch": 8,
                                "experts_held": 8})
    for name in READERS:
        if name != "lfm2_load_imbalance":
            assert reader(name).read(empty) is None, name
            assert reader(name).read(dict(traced, trace=None)) is None, name
        assert reader(name).read(other) is None, name
    monkeypatch.setattr(phases, "moe_counts", lambda: {
        "held": 0, "absent": 0, "busiest": 0})
    assert reader("lfm2_load_imbalance").read(traced) is None
    assert reader("lfm2_step_mfu").read(traced) is None


def test_benchmark_json_config_and_workload_hold_what_the_issue_asks(cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["workloads"] if c["name"] == CELL]
    assert entry == dict(entry, config=CONFIG, traffic="final", chips=1)
    names = [c["name"] for c in bench["workloads"]]
    assert names.index(CELL) > names.index("joyai-flash-final")
    assert not any(c["chips"] == 4 for c in bench["workloads"])
    (tph,) = [m for m in bench["end_to_end"]
              if m["name"] == "trials_per_hour"]
    assert CELL in tph["workloads"][2:] and tph["bound"] == 0.06
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(READERS) + [
        "lfm2." + name for name in LIFECYCLE]
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + len(mine)] == mine
    assert all(CELL not in m["workloads"]
               for m in bench["per_layer"][:first])
    assert all(m["moves"] == "trials_per_hour" for m in mine)
    for m in mine:
        assert os.path.exists(os.path.join(
            BENCH, "metrics", m["name"].rsplit(".", 1)[-1] + ".py"))
    (listed,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    _, config = cell
    reduced = ["num_hidden_layers", "layer_types", "num_dense_layers",
               "num_experts", "vocab_size", "max_position_embeddings"]
    assert sorted(listed["reduced"]) == sorted(config["reduced"]) \
        == sorted(reduced)
    assert listed["source"] == config["source"] \
        and listed["source"].endswith("LFM2-8B-A1B/blob/main/config.json")
    # every width as published; the router 32 wide, 4 a token, 8 held
    published = config["published"]
    for key, value in published.items():
        assert key in reduced or config[key] == value, key
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["num_experts_per_tok"],
            config["conv_L_cache"]) == (2048, 7168, 1792, 32, 8, 4, 3)
    assert config["router_experts"] == published["num_experts"] == 32
    assert (config["num_experts"], config["first_expert"]) == (8, 0)
    assert config["vocab_size"] * 4 == published["vocab_size"]
    assert config["layer_types"] == published["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert published["layer_types"].count("full_attention") == 6
    assert {"deployment", "assumed", "departures"} <= set(config)
    assert "4 expert-parallel chips" in config["deployment"]
    # the job's fixed knobs, number for number
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        workload = json.load(f)
    assert workload["job"] == {
        "fixed": {"train_steps": 32, "learning_rate": 2.2e-4},
        "search": {}, "budget": {"MODEL_TRIAL_COUNT": 100000}}
    knobs, _ = cell
    assert (knobs["batch_size"], knobs["seq_len"], knobs["remat"],
            knobs["steps_per_dispatch"], knobs["quick_train"]) == (
                1, 8192, "dots", 8, False)
    # every number compare.py gives is named; the precision is held by
    # the whole update, the widest dispatch's loss and the norms of the
    # parameters' change (each under the float8 control's smallest
    # reading), the bias by state_gap, gross faults by the first
    # dispatch's loss; the routed leaves' update is shown and not judged
    # (its control reads under 3 times its sound runs)
    limits = workload["limits"]
    assert set(limits) == {"loss_gap", "loss_gap_first", "dparam_gap",
                           "update_gap", "routed_gap", "state_gap"}
    assert "limits_more" not in workload
    assert limits["routed_gap"] is None
    for name in ("update_gap", "loss_gap", "loss_gap_first", "state_gap",
                 "dparam_gap"):
        assert 0 < limits[name] < 1, name
    # PERF.md section 4's readings: the largest of 17 sound seeds and the
    # smallest of the control's 3 lie on either side of each precision limit
    for name, sound, control in (("update_gap", 0.0908, 0.3922),
                                 ("loss_gap", 3.89e-4, 1.97e-2),
                                 ("dparam_gap", 9.0e-4, 4.6e-3)):
        assert sound < limits[name] < control, name


def test_the_whole_command_runs_the_class_at_a_toy_size():
    """``benchmarks/run.py`` on ``selftest/workloads/tiny-lfm2-final``:
    the template is uploaded, ``create_train_job`` trains it, the
    reference follows a trial of the window and ``correct`` is true,
    with the routed leaves and the bias state among the numbers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny-lfm2-final", "--seed", "3000000019", "--seconds", "2",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["compared"]["routed_gap"]["leaves"] == 16
    assert line["compared"]["state_gap"]["limit"] is not None
    assert {"trials_per_hour", "setup_s"} <= set(line["metrics"])
