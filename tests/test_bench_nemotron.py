"""The benchmark's files for the ``nemotron-mamba-final`` cell: the work
counts (``benchmarks/flops_nemotron.py``) against a hand count and
against the model's own ``_flops_per_step``; the new readers on a
hand-made trace and on a compiled program; the configuration, workload
and ``BENCHMARK.json`` entries; the whole command at a toy size on the
CPU."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "nemotron-mamba-final"
CONFIG = "nemotron-3-nano-30b-a3b-L7-E8"
PEAKS = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
READERS = ("nemotron_step_mfu", "nemotron_ssd_ms", "nemotron_ssd_roofline",
           "nemotron_expert_roofline")
#: The readers of the scan's Pallas kernels, after the cell's others.
KERNEL_READERS = ("nemotron_ssd_kernel_ms", "nemotron_ssd_kernel_roofline")
LIFECYCLE = ("step_ms", "device_idle", "trial_nonstep_s", "eval_ms",
             "dump_ms", "persist_ms", "compile_s_per_trial", "propose_ms",
             "handover_wait_ms", "train_host_ms", "trial_unattributed_ms")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, imported as ``run.py`` imports them."""
    sys.path.insert(0, BENCH)
    try:
        import flops_nemotron
        import harness
        yield {"flops": flops_nemotron,
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
    """Nemotron-3-Nano-30B-A3B's published widths, by hand (the cut's
    arithmetic): a Mamba-2 mixer 2688 x 10,304 + 4 x 6,144 + 4096 x 2688
    = 38.7 M, attention 2 x 2688 x 128 x 34 = 23.4 M, a relu² expert
    10.0 M, the shared one 20.0 M, a router 0.34 M, the untied head
    44.0 M; 8,192 tokens; 3 x 8,192 x 6 x 8 / 128 assignments held a
    step in expectation; the scan's chunked products over the causal
    half of each 128 x 128 chunk."""
    f = bench["flops"]
    s = f.dims(cell[0])
    mamba = 2688 * (4096 + 6144 + 64) + 4 * 6144 + 4096 * 2688
    assert f.mamba_params(s) == mamba == 38_731_776
    attn = 2 * 2688 * 128 * (32 + 2)
    assert f.attention_params(s) == attn == 23_396_352
    expert = 2 * 2688 * 1856
    assert f.expert_params(s) == expert == 9_977_856
    t = 8192
    held = 3 * t * 6 * 8 / 128
    assert f.expected_held_per_step(s) == held == 9216
    scan = t * 128 * (128 * 8 + 64 * 64) + 4 * t * 64 * 128 * 64
    assert f.ssd_fwd_flops(s) == scan
    per_token = (3 * mamba + attn + 3 * (2688 * 128 + 2 * 2688 * 3712)
                 + 16384 * 2688)
    by_hand = (6 * t * per_token + 6 * expert * held
               + 6 * 32 * t * t * 128 + 3 * 3 * scan)
    assert abs(f.train_step_flops(s) - by_hand) <= 1e-12 * by_hand
    assert 14.3e12 < by_hand < 14.6e12
    # the routed experts follow the assignments REALLY held
    assert f.train_step_flops(s, 2 * held) - f.train_step_flops(s) \
        == pytest.approx(6 * expert * held)
    # the scan is memory-bound at these widths: x, Δ, B, C in, y out,
    # and the backward's dy in and four gradients out, 3 layers
    least, bound = f.ssd_least(s, PEAKS)
    assert bound == "memory"
    per_token = (2 * (2 * 4096 + 4 * 64 + 2 * 2 * 1024)
                 + 3 * 4 * 4096 + 4 * 64 + 4 * 2 * 1024)
    assert least == pytest.approx(3 * t * per_token / 819e9)
    # 384 rows an expert: the products are still bound by the MXU
    least, bound = f.experts_least(s, held, PEAKS)
    assert bound == "compute"
    assert least == pytest.approx(6 * expert * held / 197e12)


def test_chip_util_counts_the_hybrid_step(bench, cell):
    """``MfuMeter`` (``chip_util``) is fed the class's own
    ``_flops_per_step``, pinned to the benchmark's count; and the
    benchmark's own self-check finds the configuration."""
    from rafiki_tpu.models import JaxNemotronHLM

    knobs, _ = cell
    f = bench["flops"]
    mine = JaxNemotronHLM(**knobs)._flops_per_step(1)
    assert mine == pytest.approx(f.train_step_flops(f.dims(knobs)),
                                 rel=1e-12)
    assert mine == pytest.approx(
        f.train_step_flops(dict(f.dims(knobs), batch=2)) / 2, rel=1e-12)
    assert f._self_check() == 0


def _op(seconds, n, short):
    return {"seconds": seconds, "n": n, "short": short}


@pytest.fixture()
def traced(cell):
    """A hand-made run record: a traced slice of 16 steps (two train
    chunks of 8) with the ops the new readers look for."""
    knobs, _ = cell
    ops = {
        # the scan: its decays (B, H, chunks, Q, Q), a chunk's products,
        # the state carried in the loop over chunks (B, G, R, P, N)
        "%fusion.3 = f32[1,64,8,8,128,128]{5,4,3,2,1,0} fusion(f32[1,64,64,"
        "128]{3,2,1,0} %cum), kind=kLoop":
            _op(0.30, 48, "fusion.3 fusion f32[1,64,8,8,128,128]"),
        "%dot.5 = f32[1,64,128,8,8,64]{5,4,3,2,1,0} dot(bf16[1,64,8,8,128,"
        "128]{5,4,3,2,1,0} %w, bf16[1,64,128,8,8,64]{5,4,3,2,1,0} %xs)":
            _op(0.10, 48, "dot.5 dot f32[1,64,128,8,8,64]"),
        "%fusion.6 = f32[1,8,8,64,128]{4,3,2,1,0} fusion(f32[1,8,8,64,128]"
        "{4,3,2,1,0} %state, f32[1,8,8]{2,1,0} %decay)":
            _op(0.02, 3072, "fusion.6 fusion f32[1,8,8,64,128]"),
        # the loop over chunks is a container: its ops count one by one
        "%while.9 = (s32[], f32[1,8,8,64,128]{4,3,2,1,0}) while(%t)":
            _op(0.50, 48, "while.9 while s32[]"),
        # the mixer's projections and the attention are not the scan
        "%fusion.11 = bf16[8192,10304]{1,0} fusion(bf16[8192,2688]{1,0} %u,"
        " bf16[2688,10304]{1,0} %w)":
            _op(0.40, 48, "fusion.11 fusion bf16[8192,10304]"),
        "%gqa_attention.2 = (bf16[32,8192,128]{2,1,0}, f32[32,8192,8]{2,1,0})"
        " custom-call(bf16[32,8192,128]{2,1,0} %q, bf16[2,8192,128]{2,1,0} "
        '%k, bf16[2,8192,128]{2,1,0} %v), custom_call_target="tpu_custom_'
        'call"': _op(0.20, 16, "gqa_attention.2 custom-call"),
        # an expert loop, two relu² products inside it, routing outside
        "%while.1 = (s32[], f32[8192,2688]{1,0}, s32[65664]{0}, "
        "bf16[8,2688,1856]{2,1,0}) while(%tuple.1), condition=%c, body=%b":
            _op(0.60, 96, "while.1 while s32[]"),
        "%fusion.7 = f32[128,1856]{1,0} fusion(bf16[128,2688]{1,0} %x, "
        "bf16[8,2688,1856]{2,1,0} %w, s32[] %e), kind=kOutput":
            _op(0.20, 3456, "fusion.7 fusion f32[128,1856]"),
        "%fusion.9 = f32[8,1856,2688]{2,1,0} fusion(f32[8,1856,2688]{2,1,0}"
        " %dw, bf16[128,1856]{1,0} %h, bf16[128,2688]{1,0} %dy, s32[] %e)":
            _op(0.15, 1728, "fusion.9 fusion f32[8,1856,2688]"),
        "%fusion.12 = f32[8192,128]{1,0} fusion(f32[8192,2688]{1,0} %u, "
        "f32[2688,128]{1,0} %r)": _op(0.02, 96, "fusion.12 fusion "
                                      "f32[8192,128]"),
    }
    return {"trace": {"ops": ops, "window_s": 6.0, "busy_s": 5.8,
                      "programs": {"jit_train_chunk": [2.6, 2.6]}},
            "knobs": knobs, "peaks": PEAKS, "chips": 1}


def test_new_readers_on_a_hand_made_trace(bench, traced, monkeypatch):
    f = bench["flops"]
    s = f.dims(traced["knobs"])
    reader = bench["reader"]
    assert reader("nemotron_ssd_ms").seconds(traced) == pytest.approx(0.42)
    assert reader("nemotron_ssd_ms").read(traced) == pytest.approx(
        1e3 * 0.42 / 16)
    least, _ = f.ssd_least(s, PEAKS)
    assert reader("nemotron_ssd_roofline").read(traced) == pytest.approx(
        100 * least / (0.42 / 16))
    assert reader("nemotron_expert_roofline").split(traced) == \
        pytest.approx({"loops": 0.60, "products": 0.35})
    # the counters are the program's own, cumulative over the run
    from rafiki_tpu.observe import phases

    monkeypatch.setattr(phases, "moe_counts", lambda: {
        "held": 9000 * 40, "absent": 138456 * 40, "busiest": 3000 * 40})
    monkeypatch.setattr(phases, "phase_totals", lambda: {
        "step_wait": {"count": 5, "sum": 1.0}})
    held = 9000.0  # a step: 40 steps dispatched
    least, bound = f.experts_least(s, held, PEAKS)
    assert bound == "compute"
    assert reader("nemotron_expert_roofline").read(traced) == \
        pytest.approx(100 * least / (0.35 / 16))
    assert reader("nemotron_step_mfu").read(traced) == pytest.approx(
        100 * 16 * f.train_step_flops(s, held) / (6.0 * 197e12))
    for name in READERS:
        value = reader(name).read(traced)
        assert value > 0, name
        if name != "nemotron_ssd_ms":  # shares of a peak stay shares
            assert value < 100, name
    # nothing to read: no number, no error; another class's run: none
    empty = dict(traced, trace=dict(traced["trace"], ops={}, programs={}))
    other = dict(traced, knobs={"steps_per_dispatch": 8,
                                "experts_held": 8, "layer_types": ["conv"]})
    for name in READERS:
        assert reader(name).read(empty) is None, name
        assert reader(name).read(dict(traced, trace=None)) is None, name
        assert reader(name).read(other) is None, name
    monkeypatch.setattr(phases, "moe_counts", lambda: {
        "held": 0, "absent": 0, "busiest": 0})
    assert reader("nemotron_step_mfu").read(traced) is None
    assert reader("nemotron_expert_roofline").read(traced) is None


@pytest.fixture()
def traced_kernels(cell):
    """A hand-made run record of a program whose scan runs as kernels:
    a traced slice of 16 steps with two named ``ssd_`` kernels, a flash
    kernel and a Mosaic call without a name."""
    knobs, _ = cell
    target = 'custom_call_target="tpu_custom_call"'

    def call(name, results, operands, metadata):
        return (f"%{name} = {results} custom-call({operands}), "
                f"custom_call_target=\"tpu_custom_call\", "
                f"frontend_attributes={{kernel_metadata={{{metadata}}}}}")

    ops = {
        call("ssd_scan.4", "(f32[1,8192,4096]{2,1,0}, f32[32,8192,128]"
             "{2,1,0})", "f32[1,8192,4096]{2,1,0} %x, f32[1,64,8192]{2,1,0}"
             " %dt", '"kernel":"ssd_fwd"'):
            _op(0.045, 48, "ssd_scan.4 custom-call"),
        call("ssd_scan.9", "(f32[1,8192,4096]{2,1,0}, f32[1,64,8192]"
             "{2,1,0})", "f32[1,8192,4096]{2,1,0} %x, f32[32,8192,128]"
             "{2,1,0} %states", '"kernel" : "ssd_bwd"'):
            _op(0.123, 48, "ssd_scan.9 custom-call"),
        call("gqa_attention.2", "(bf16[32,8192,128]{2,1,0}, f32[32,8192,8]"
             "{2,1,0})", "bf16[32,8192,128]{2,1,0} %q", '"kernel":"flash_fwd"'):
            _op(0.20, 16, "gqa_attention.2 custom-call"),
        f"%custom-call.7 = f32[8,128]{{1,0}} custom-call(f32[8,128]{{1,0}} "
        f"%z), {target}": _op(0.01, 16, "custom-call.7 custom-call"),
        # the scan's name on an op that is no Mosaic call
        '%fusion.2 = f32[1,8192,4096]{2,1,0} fusion(f32[1,8192,4096]{2,1,0}'
        ' %y), metadata={op_name="ssd_fwd"}': _op(0.30, 48, "fusion.2 "
                                                   "fusion"),
    }
    return {"trace": {"ops": ops, "window_s": 6.0, "busy_s": 5.8,
                      "programs": {"jit_train_chunk": [2.6, 2.6]}},
            "knobs": knobs, "peaks": PEAKS, "chips": 1}


def test_scan_kernel_readers_on_a_hand_made_trace(bench, traced_kernels):
    """The device time of the ``ssd_`` kernels a step, and the scans'
    least time (the one ``nemotron_ssd_roofline`` divides) over it;
    nothing from the flash kernel, the unnamed call or an op that only
    mentions the name."""
    f = bench["flops"]
    reader = bench["reader"]
    kernel_ms = reader("nemotron_ssd_kernel_ms")
    assert kernel_ms.seconds(traced_kernels) == pytest.approx(0.168)
    assert kernel_ms.read(traced_kernels) == pytest.approx(
        1e3 * 0.168 / 16)
    least, bound = f.ssd_least(f.dims(traced_kernels["knobs"]), PEAKS)
    assert bound == "memory"
    share = reader("nemotron_ssd_kernel_roofline").read(traced_kernels)
    assert share == pytest.approx(100 * least / (0.168 / 16))
    assert 0 < share < 100
    # the jax.numpy scan's ops are not the kernels', and not these
    # readers' to count: nemotron_ssd_ms reads them
    assert reader("nemotron_ssd_ms").read(traced_kernels) is None


@pytest.mark.parametrize("case", ["no scan kernel", "no trace", "no peaks",
                                  "another class", "no steps"])
def test_scan_kernel_readers_have_nothing_to_read(bench, traced, cell,
                                                  traced_kernels, case):
    """Null, and no error, where no ``ssd_`` kernel ran (the ``jax.numpy``
    scan's trace, as the parent's program makes it), where there is no
    trace, no peak table (the share), another class's run, or no step
    in the slice."""
    knobs, _ = cell
    run = {"no scan kernel": traced,
           "no trace": dict(traced_kernels, trace=None),
           "no peaks": dict(traced_kernels, peaks=None),
           "another class": dict(traced_kernels, knobs={
               "steps_per_dispatch": 8, "layer_types": ["conv"]}),
           "no steps": dict(traced_kernels, trace=dict(
               traced_kernels["trace"], programs={}))}[case]
    reader = bench["reader"]
    if case != "no peaks":
        assert reader("nemotron_ssd_kernel_ms").read(run) is None
    assert reader("nemotron_ssd_kernel_roofline").read(run) is None


def _device_ops(text):
    """A compiled program's ops as a profiler's op events name them:
    the instructions of the entry and of the loops' bodies (not of
    fusions, which run as one op), each operand written with its
    defining instruction's shape. {event text: (op, the line with its
    metadata)}."""
    shape_of = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = (\(?\w+\[[\d,]*\][^ ]*) ", text, re.M))
    ops = {}
    for computation in re.split(r"\n(?=\S)", text):
        head = computation.split("\n", 1)[0].split(" ")[0]
        if not head.startswith(("%", "ENTRY")) or "fus" in head:
            continue
        for line in computation.split("\n")[1:]:
            found = re.match(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*)$", line)
            if not found:
                continue
            name, rest = found.groups()
            call = re.search(r"\s([a-z][\w\-]*)\((.*?)\)(,|$)", rest)
            if not call or call.group(1) in (
                    "parameter", "constant", "get-tuple-element", "tuple",
                    "bitcast"):
                continue
            operands = ", ".join(
                f"{shape_of.get(o, '')} {o}"
                for o in re.findall(r"%[\w.\-]+", call.group(2)))
            text_of_op = f"{name} = {rest[:call.start()]} " \
                         f"{call.group(1)}({operands})"
            ops[text_of_op] = (_op(1.0, 1, f"{name[1:]} {call.group(1)}"),
                               line)
    return ops


def test_readers_on_a_compiled_program(bench):
    """The tiny configuration's whole train step compiled on the CPU
    (8 Mamba heads of 8 lanes, state 12, chunks of 32 over 96
    positions, so that no other array holds the scan's (3, 32), (32,
    32) or (8, 12)): the scan's reader counts every batched product of
    the ``ssd_scan`` scope and no op of the experts' or the attention's
    scope; the experts' reader finds the relu² loops and products. On
    one device, as one chip runs it (the tests' process has eight)."""
    import jax
    import jax.numpy as jnp
    import optax

    from rafiki_tpu.models import JaxNemotronHLM
    from rafiki_tpu.models.lm import _weights
    from rafiki_tpu.parallel import build_mesh

    with open(os.path.join(BENCH, "selftest", "configs",
                           "tiny-nemotron.json")) as f:
        knobs = dict(bench["flops"].knobs_of(json.load(f)), seq_len=96,
                     ssm_chunk=32, mamba_head_dim=8, ssm_state=12)
    model = JaxNemotronHLM(**knobs)
    model._mesh = build_mesh(jax.devices()[:1])
    params = model._init_params()
    tx = optax.adamw(1e-3)
    wins = jnp.zeros((2, 2, 97), jnp.int32)
    text = model._make_train_chunk(tx).lower(
        params, tx.init(_weights(params)), wins).compile().as_text()
    ops = _device_ops(text)
    run = {"trace": {"ops": {k: op for k, (op, _) in ops.items()}},
           "knobs": knobs}
    scan = bench["reader"]("nemotron_ssd_ms")
    pairs = scan.signature(bench["flops"].dims(knobs))
    shapes_in = bench["reader"]("moe_expert_roofline").shapes_in
    counted = {t for t in ops if any(scan.is_scan(shape, pairs)
                                     for shape in shapes_in(t))
               and ops[t][0]["short"].split(" ")[1] not in (
                   "while", "call", "conditional", "custom-call")}
    assert scan.seconds(run) == len(counted) > 0
    products = {t for t, (op, line) in ops.items()
                if "ssd_scan" in line and op["short"].endswith(" dot")
                and len(re.match(r"%\S+ = \w+\[([\d,]*)\]", t).group(1)
                        .split(",")) >= 4}
    assert products and products <= counted
    assert not [t for t in counted
                if re.search(r"moe_ffn|gqa_attention", ops[t][1])]
    parts = bench["reader"]("nemotron_expert_roofline").split(run)
    assert parts is not None and parts["products"] > 0


def test_benchmark_json_config_and_workload_hold_what_the_issue_asks(cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [c for c in bench["workloads"] if c["name"] == CELL]
    assert entry == dict(entry, config=CONFIG, traffic="final", chips=1)
    assert bench["workloads"][-1] is entry
    assert not any(c["chips"] == 4 for c in bench["workloads"])
    (tph,) = [m for m in bench["end_to_end"]
              if m["name"] == "trials_per_hour"]
    assert tph["workloads"][-1] == CELL and tph["bound"] == 0.06
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == list(READERS) + [
        "nemo." + name for name in LIFECYCLE] + list(KERNEL_READERS)
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + len(mine)] == mine
    assert all(CELL not in m["workloads"]
               for m in bench["per_layer"][:first])
    assert all(m["moves"] == "trials_per_hour" for m in mine)
    for m in mine:
        assert os.path.exists(os.path.join(
            BENCH, "metrics", m["name"].rsplit(".", 1)[-1] + ".py"))
    (listed,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert bench["configs"][-1] is listed
    _, config = cell
    reduced = ["num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size", "max_position_embeddings"]
    assert sorted(listed["reduced"]) == sorted(config["reduced"]) \
        == sorted(reduced)
    assert listed["source"] == config["source"] and listed["source"] \
        .endswith("NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    # every width as published; the router 128 wide, 6 a token, 8 held
    published = config["published"]
    for key, value in published.items():
        assert key in reduced or config[key] == value, key
    assert (config["hidden_size"], config["mamba_num_heads"],
            config["mamba_head_dim"], config["ssm_state_size"],
            config["n_groups"], config["conv_kernel"], config["chunk_size"],
            config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["num_experts_per_tok"]) == (
                2688, 64, 64, 128, 8, 4, 128, 1856, 3712, 32, 2, 128, 6)
    assert config["router_experts"] == published["n_routed_experts"] == 128
    assert (config["n_routed_experts"], config["first_expert"]) == (8, 0)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    # one whole period: the published string is it five times over
    assert config["hybrid_override_pattern"] == "MEMEM*E" == \
        published["hybrid_override_pattern"][:7]
    assert published["hybrid_override_pattern"][:35] == "MEMEM*E" * 5
    assert config["num_hidden_layers"] == 7
    assert {"deployment", "assumed", "departures"} <= set(config)
    assert "16 expert-parallel chips" in config["deployment"]
    assert {"learning_rate", "bias_update_rate", "gate_normaliser", "init",
            "positional_encoding"} <= set(config["assumed"])
    assert any("residual" in d for d in config["departures"])
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
    # every number compare.py gives is named; the biases are state
    limits = workload["limits"]
    assert set(limits) == {"loss_gap", "loss_gap_first", "dparam_gap",
                           "update_gap", "routed_gap", "state_gap"}
    assert limits["state_gap"] is not None
    assert limits["update_gap"] is not None or limits["routed_gap"] \
        is not None
    for name, limit in limits.items():
        assert limit is None or 0 < limit < 1, name


def test_the_whole_command_runs_the_class_at_a_toy_size():
    """``benchmarks/run.py`` on ``selftest/workloads/tiny-nemotron-final``:
    the template is uploaded, ``create_train_job`` trains it, the
    reference follows a trial of the window and ``correct`` is true,
    with the routed leaves and the bias state among the numbers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny-nemotron-final", "--seed", "3000000019", "--seconds", "2",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["device"]["platform"] == "cpu" and line["failed"] == 0
    assert line["compared"]["routed_gap"]["leaves"] == 9
    assert line["compared"]["state_gap"]["limit"] is not None
    assert {"trials_per_hour", "setup_s"} <= set(line["metrics"])
