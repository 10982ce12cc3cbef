#!/usr/bin/env python3
"""The benchmark's own checks, on the CPU at a toy size. Not in
BENCHMARK.json, not under tests/.

    JAX_PLATFORMS=cpu python3 benchmarks/selftest/run_selftest.py [name ...]

``flops``      flops.py against models/lm.py's arithmetic (2.114e13 at
               the Pythia cell's shapes).
``schema``     BENCHMARK.json against the files it names: every cell has
               its workload and configuration file and they agree, every
               metric its reader.
``command``    the whole command, ``tiny-final`` and ``tiny-search``: a
               contract-shaped last line, platform "cpu", ``correct``
               true, end-to-end metrics with --trace 0 and no device
               metric with --trace 1.
``control``    control.py at the toy size: the reference in the next
               precision below (float8 operands) put in the program's
               place fails the toy limits by ``compare.judge``, and so
               does the reference with half of the batch left out; the
               reference with bfloat16 operands passes.
``compare``    test_compare.py beside this file: ``compare.py`` on trees
               made there, and the sparse-expert reference's ``bf16``
               and ``fp8`` modes at a toy size, parted by
               ``update_gap`` and ``routed_gap``.
``faults``     the rest of a run with the timed path broken underneath
               comes out ``correct`` false: a step that returns its
               state unchanged; half of the batch left out and the mean
               taken over the rest; the parameters altered as the trial
               hands them to the param store.
``trace``      trace_reduce.py and the trace readers on the recorded TPU
               trace kept beside this file (one 16-step trial at the
               Pythia cell's shapes), against numbers read off it by
               plain sums.
``no_tpu``     a measurement workload refuses to run without a TPU.

Exit code 0 only if every check named (default: all) passes.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)


def run_cell(workload: str, seed: int, trace: int = 0, seconds: float = 2):
    """The command in this process; returns the parsed last line."""
    import run as bench_run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        code = bench_run.main(["--workload", workload, "--seed", str(seed),
                               "--seconds", str(seconds),
                               "--trace", str(trace)])
    assert code == 0, code
    return json.loads(out.getvalue().strip().splitlines()[-1])


def check_flops():
    import flops
    import flops_moe

    assert flops._self_check() == 0
    assert flops_moe._self_check() == 0
    config = json.load(open(os.path.join(
        BENCH, "configs", "pythia-1.4b-L6.json")))
    got = flops.train_step_flops(flops.shapes_of(config))
    assert abs(got - 2.114e13) < 0.001e13, got


def check_schema():
    import compare
    from harness import load_module

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        path = os.path.join(BENCH, "workloads", cell["name"] + ".json")
        workload = json.load(open(path))
        for key in ("name", "config", "traffic", "chips"):
            assert workload[key] == cell[key], (cell["name"], key)
        assert os.path.exists(os.path.join(
            BENCH, "drivers", workload["driver"] + ".py"))
        entry = configs[cell["config"]]
        config = json.load(open(os.path.join(ROOT, entry["file"])))
        assert config["source"] == entry["source"]
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        for key in entry["reduced"]:
            assert config[key] != config["published"][key], key
        for key, value in config["published"].items():
            assert key in entry["reduced"] or config[key] == value, key
        for kind in ("templates", "data", "reference"):
            name = {"templates": config["template"] + ".py.tmpl",
                    "data": config["data"]["generator"] + ".py",
                    "reference": config["reference"] + ".py"}[kind]
            assert os.path.exists(os.path.join(BENCH, kind, name)), name
        # The limits name numbers compare.py gives; null says "shown,
        # not judged". A cell holds its precision by a number of the
        # update, update_gap or routed_gap; one whose reference names
        # state limits state_gap.
        limits = compare.limits_of(workload)
        assert set(limits) <= set(compare.NUMBERS), limits
        held = {k: v for k, v in limits.items() if v is not None}
        assert all(0 < limit < 1 for limit in held.values()), limits
        has_state = compare.kinds_of(load_module(
            "reference", config["reference"]))["is_state"] is not None
        assert ("state_gap" in held) == has_state, cell["name"]
        assert "update_gap" in held or "routed_gap" in held, cell["name"]
    names = {c["name"] for c in bench["workloads"]}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in end_to_end
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "metrics", metric["name"].rsplit(".", 1)[-1] + ".py")), \
            metric["name"]
        assert set(metric.get("workloads", names)) <= names
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", metric["unit"])
        assert metric["better"] in ("lower", "higher")
    for metric in bench["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1, metric
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in bench["per_layer"]:
        assert metric["moves"] in end_to_end, metric
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}, metric
    # The contract's limits on names and lines.
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    for entry in (bench["configs"] + bench["workloads"]
                  + bench["end_to_end"] + bench["per_layer"]):
        assert name.fullmatch(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            line = entry.get(key, "x")
            assert 1 <= len(line) <= 200 and "\n" not in line \
                and "\t" not in line, (entry["name"], key, len(line))
    for cell in bench["workloads"]:
        assert name.fullmatch(cell["traffic"]) and cell["chips"] in (1, 4)
    assert 1 <= bench["run_seconds"] <= 51
    for root, _, files in os.walk(BENCH):
        if "__pycache__" in root:
            continue
        for file in files:
            assert re.fullmatch(r"[A-Za-z0-9_.\-]+", file), file


def check_command():
    end_to_end = {m["name"] for m in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["end_to_end"]}
    for workload in ("tiny-final", "tiny-search"):
        line = run_cell(workload, seed=3000000019)
        assert list(line)[:5] == ["correct", "attempted", "failed",
                                  "metrics", "device"], list(line)
        assert list(line)[-1] == "compared"
        assert line["correct"] is True, line
        assert line["device"]["platform"] == "cpu"
        assert line["attempted"] >= 1 and line["failed"] == 0
        assert set(line["metrics"]) == end_to_end  # a selftest owes all
        for pair in line["compared"].values():
            assert pair["limit"] is None or pair["value"] <= pair["limit"]
    traced = run_cell("tiny-final", seed=7, trace=1)
    assert traced["metrics"] == {}, traced["metrics"]  # no device metric
    assert traced["correct"] is True


def check_control():
    import compare
    import control

    config = json.load(open(os.path.join(HERE, "configs", "tiny-lm.json")))
    workload = json.load(open(os.path.join(
        HERE, "workloads", "tiny-final.json")))
    limits = compare.limits_of(workload)
    assert "update_gap" in limits, limits
    got = control.readings(
        config, workload["job"], 5,
        workload["job"]["fixed"]["learning_rate"],
        variants=(("bf16", ""),) + control.VARIANTS)
    for variant, numbers in got.items():
        compared, correct = compare.judge(numbers, limits)
        print(f"  {variant}: correct={correct}", compared)
        assert correct is (variant == "bf16"), variant
        if variant == "fp8":  # the precision step, held by update_gap
            assert compared["update_gap"]["value"] \
                > compared["update_gap"]["limit"]


def check_compare():
    import pytest

    assert pytest.main([os.path.join(HERE, "test_compare.py"), "-q",
                        "-p", "no:cacheprovider"]) == 0


def check_faults():
    """Break the program's step underneath and drive the rest of a run.
    The breaks are planted where the step is traced, so the compiled
    program the window drives is the broken one."""
    import jax
    import optax

    from rafiki_tpu.model import jax_model

    def unchanged():
        keep = optax.apply_updates
        optax.apply_updates = lambda params, updates: params
        return lambda: setattr(optax, "apply_updates", keep)

    def half_batch():
        keep = jax.lax.with_sharding_constraint

        def drop(x, sharding):
            x = keep(x, sharding)
            return x[:x.shape[0] // 2] if x.ndim == 2 else x

        jax.lax.with_sharding_constraint = drop
        return lambda: setattr(jax.lax, "with_sharding_constraint", keep)

    def answer_altered():
        from rafiki_tpu.models import JaxTransformerLM

        keep = JaxTransformerLM.dump_parameters

        def dump(self):
            out = keep(self)
            out["layers/w2"] = out["layers/w2"][::-1]  # layers swapped
            return out

        JaxTransformerLM.dump_parameters = dump
        return lambda: setattr(JaxTransformerLM, "dump_parameters", keep)

    for name, plant in (("state unchanged", unchanged),
                        ("half batch", half_batch),
                        ("answer altered", answer_altered)):
        jax_model._STEP_CACHE.clear()
        restore = plant()
        try:
            line = run_cell("tiny-final", seed=11)
        finally:
            restore()
            jax_model._STEP_CACHE.clear()
        print(f"  {name}: correct={line['correct']}", line["compared"])
        assert line["correct"] is False, (name, line)
    sound = run_cell("tiny-final", seed=11)
    assert sound["correct"] is True, sound


def check_trace():
    """trace_reduce.py and the four trace readers on the recorded trace
    of one 16-step trial of the program at the Pythia cell's shapes."""
    import gzip

    from jax.profiler import ProfileData

    import flops
    import trace_reduce
    from harness import load_module

    expected = json.load(open(os.path.join(HERE, "lm14_trial.expected.json")))
    with gzip.open(os.path.join(HERE, "lm14_trial.xplane.pb.gz")) as f:
        planes = ProfileData.from_serialized_xspace(f.read()).planes
    got = trace_reduce.reduce_planes(planes, chips=1)

    def close(a, b):
        return abs(a - b) <= 1e-9 + 1e-9 * abs(b)

    for key in ("busy_s", "span_s"):
        assert close(got[key], expected[key]), (key, got[key])
    assert got["busy_s"] <= got["span_s"]
    for name, want in expected["programs"].items():
        have = got["programs"][name]
        assert len(have) == want["n"] and close(
            sum(have), want["seconds"]), (name, have, want)

    config = json.load(open(os.path.join(
        BENCH, "configs", "pythia-1.4b-L6.json")))
    run = {"trace": dict(got, window_s=expected["span_s"]),
           "shapes": flops.shapes_of(config), "chips": 1,
           "peaks": flops.load_peaks("TPU v5 lite"),
           "knobs": {"steps_per_dispatch": 8}}
    kernel_calls = load_module("metrics", "attn_fwd_roofline").kernel_calls
    kernels = expected["kernels"]
    for name, signature in (("forward", (3, 2)), ("dq", (6, 1)),
                            ("dkv", (6, 2))):
        have = kernel_calls(run, *signature)
        assert have["n"] == kernels[name]["n"] and close(
            have["seconds"], kernels[name]["seconds"]), (name, have)
    # 2 x 2 x 64 x 2048^2 x 128 / 2 FLOPs a forward call at 197e12/s.
    fwd_least = 2 * 2 * 64 * 2048 ** 2 * 128 / 2 / 197e12
    values = {name: load_module("metrics", name).read(run) for name in
              ("step_ms", "step_mfu", "attn_fwd_roofline",
               "attn_bwd_roofline", "device_idle")}
    print("  readers on the recorded trial:", values)
    assert close(values["attn_fwd_roofline"], 100 * fwd_least
                 / (kernels["forward"]["seconds"] / kernels["forward"]["n"]))
    assert close(values["attn_bwd_roofline"], 100 * 2 * fwd_least * 96 / (
        kernels["dq"]["seconds"] + kernels["dkv"]["seconds"]))
    assert close(values["step_ms"], 1e3 * expected["programs"][
        "jit_train_chunk"]["seconds"] / 16)
    assert abs(values["step_mfu"] - 100 * 16 * 2.114412e13 / 197e12
               / expected["span_s"]) < 1e-4
    assert close(values["device_idle"], 100 * (
        1 - expected["busy_s"] / expected["span_s"]))
    assert all(0 < v < 100 for name, v in values.items()
               if name != "step_ms")
    # A slice of the window cuts into the execution in flight at each
    # end (durations as the trace of seed 777000111's run held them).
    cut = dict(run, trace=dict(got, window_s=16.05, programs={
        "jit_train_chunk": [0.293] + [1.5241] * 7 + [1.0727]}))
    assert close(load_module("metrics", "step_ms").read(cut), 190.5125)
    assert abs(load_module("metrics", "step_mfu").read(cut) - 42.243) < 0.005
    broken = dict(run, trace=dict(got, ops={}, programs={}))
    assert all(load_module("metrics", name).read(broken) is None
               for name in ("step_ms", "step_mfu", "attn_fwd_roofline",
                            "attn_bwd_roofline")), "a reader made a number"
    spans = [("a", got["gaps"][0][0], got["gaps"][0][1])]
    parts = trace_reduce.breakdown(got, spans)
    assert parts["idle_gaps"][0][0] == "a" and len(parts["device_ops"]) == 10
    assert not any(name.endswith(" while") for name, _ in parts["device_ops"])
    # The slice's idle edges count: 5 s after the last op, to the window's end.
    edged = trace_reduce.breakdown(
        dict(got, window_s=got["last_ns"] / 1e9 + 5.0),
        [("edge", got["last_ns"], got["last_ns"] + 5e9)])
    assert close(dict(map(tuple, edged["idle_gaps"]))["edge"], 5.0)
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) \
        == [[0, 3], [5, 8]]


def check_no_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
         ["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and not proc.stdout.strip(), proc


CHECKS = {"flops": check_flops, "schema": check_schema,
          "control": check_control, "compare": check_compare,
          "trace": check_trace,
          "no_tpu": check_no_tpu, "command": check_command,
          "faults": check_faults}


def main(argv) -> int:
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise SystemExit("set JAX_PLATFORMS=cpu: the selftest never "
                         "touches a chip")
    failed = []
    for name in argv or list(CHECKS):
        print(f"[selftest] {name} ...", flush=True)
        try:
            CHECKS[name]()
            print(f"[selftest] {name}: ok", flush=True)
        except Exception as exc:  # report every check, then fail
            import traceback

            traceback.print_exc()
            failed.append(name)
            print(f"[selftest] {name}: FAILED ({exc!r})"[:400], flush=True)
    print(f"[selftest] {'FAILED: ' + ', '.join(failed) if failed else 'all ok'}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
