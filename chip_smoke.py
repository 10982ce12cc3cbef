#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once — train -> deploy -> predict / generate — through
the entry points a user calls (``LocalPlatform`` composed from a
``NodeConfig`` as the serve CLI composes it, ``rafiki_tpu.client.Client``
over HTTP), at the full width of the repo's flagship LM: d_model 2048,
8 layers, 16 heads x 128, seq_len 2048, vocab 32768, batch 4, bf16 compute,
remat "dots". Weights are random from a seed, data is a seeded Markov
stream; the steps and requests are few, the cost is compiling.

    python3 chip_smoke.py             one chip: kernel numerics, one
                                      COMPLETED trial, /predict, /generate,
                                      on-device residency, compiled kernel
    python3 chip_smoke.py --chips 4   ONLY the four-chip path and what it
                                      is compared with: dp=4 vs a one-chip
                                      group that is not device 0, then four
                                      one-chip replicas behind one Predictor

One process holds the chip; nothing here starts a python that imports jax.
Any failed phase, assert or non-TPU device exits non-zero and prints no
result line. The last line of stdout is the result and nothing more:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Figures printed on earlier lines are smoke figures (a few dozen steps,
compile included where it says so) — not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

SEED = 0

#: The flagship LM (2048 wide, 8 layers), pinned by
#: FixedKnobs so the advisor has nothing to search: one trial = one shape.
FLAGSHIP = {
    "d_model": 2048, "n_layers": 8, "seq_len": 2048, "vocab_size": 32768,
    "batch_size": 4, "learning_rate": 3e-4,
    # six dispatches of steps_per_dispatch=8
    "train_steps": 48,
}
F32_WEIGHT_BYTES = 1.88e9  # 470M parameters held in f32

#: Uploaded through ``create_model(model_source=...)`` — the upstream
#: upload-a-model-file flow.
MODEL_SOURCE = '''
from rafiki_tpu.model import FixedKnob
from rafiki_tpu.models import JaxTransformerLM


class FlagshipLM(JaxTransformerLM):
    """JaxTransformerLM pinned to one shape."""

    @staticmethod
    def get_knob_config():
        knobs = dict(JaxTransformerLM.get_knob_config())
        knobs.update({name: FixedKnob(value)
                      for name, value in %(knobs)r.items()})
        knobs["quick_train"] = FixedKnob(False)
        knobs["seed"] = FixedKnob(%(seed)d)
        return knobs
'''

#: dp=4 against one chip, same seed and global batch: the same windows in
#: the same order, so each logged loss differs only by what bf16 matmul
#: tiling and the gradient all-reduce's summation order let drift apart
#: over a few dozen Adam steps.
DP_LOSS_RTOL = 5e-3


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Wall time of one phase. A failure is not caught: it ends the run."""
    t0 = time.monotonic()
    say(f"[phase] {name} ...")
    yield
    say(f"[phase] {name}: {time.monotonic() - t0:.1f}s")


# --- the device -------------------------------------------------------


def device_report():
    """Resolve the platform (raises without a TPU), print what is cheap
    and useful, return ``{"platform", "kind", "count"}`` as jax reports."""
    from rafiki_tpu.jaxenv import ensure_platform

    platform = ensure_platform()
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; the platform resolved to "
            f"{platform!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r})")
    import jax
    import jaxlib

    from rafiki_tpu.observe.profiling import _PEAK_FLOPS_BY_KIND

    devices = jax.devices()
    dev = devices[0]
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a printed string, no more
        libtpu = "unknown"
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu}  python {sys.version.split()[0]}")
    say(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)}")
    if not any(kind in dev.device_kind for kind in _PEAK_FLOPS_BY_KIND):
        raise SystemExit(f"device kind {dev.device_kind!r} is not in the "
                         f"peak-FLOP/s table (observe/profiling.py)")
    say(f"compile cache: {jax.config.jax_compilation_cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    say(f"g++: {shutil.which('g++') or 'absent'} (builds the native "
        f"broker for tcp:// buses; this path uses the in-process bus)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


class CompileCounters:
    """What compiling cost, from jax's own monitoring events: seconds per
    program (backend compile, or retrieval on a cache hit) and the
    persistent cache's requests / hits / entries written."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"
    WRITTEN = "/jax/compilation_cache/cache_misses"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.counts = {self.REQUEST: 0, self.HIT: 0, self.WRITTEN: 0}
        self.programs = []  # (fun_name, seconds)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_):
        if event in self.counts:
            self.counts[event] += 1

    def _on_duration(self, event: str, seconds: float, **kw):
        if event == self.COMPILE:
            self.programs.append((str(kw.get("fun_name")), seconds))

    def report(self) -> None:
        total = sum(sec for _, sec in self.programs)
        slow = [(n, sec) for n, sec in self.programs if sec >= 1.0]
        say(f"compile: {len(self.programs)} programs, {total:.1f}s in "
            f"all; those over 1s: "
            + ", ".join(f"{n} {sec:.1f}s" for n, sec in slow))
        say(f"compile cache: {self.counts[self.REQUEST]} requests, "
            f"{self.counts[self.HIT]} hits, "
            f"{self.counts[self.WRITTEN]} entries written")


def peak_bytes(device) -> int:
    stats = device.memory_stats()
    assert stats and "peak_bytes_in_use" in stats, \
        f"{device} reports no memory stats: {stats!r}"
    return int(stats["peak_bytes_in_use"])


# --- phase: the compiled kernel is right, not only present ------------


def check_kernel() -> None:
    """flash_attention(interpret=False) against naive_attention, forward
    and jax.grad, causal: at the flagship's T=2048 x D=128 in bf16, and at
    the small-block shape that once failed Mosaic lowering (f32, t=256,
    d=64, block_q=32, block_kv=64). Tolerances: the 2e-2 that the TPU
    regression test of that shape stated."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rafiki_tpu.ops import flash_attention, naive_attention

    cases = [
        ("T2048 D128 bf16", (1, 4, 2048, 128), jnp.bfloat16, {}),
        ("T256 D64 f32 blocks 32/64", (1, 1, 256, 64), jnp.float32,
         {"block_q": 32, "block_kv": 64}),
    ]
    for name, shape, dtype, blocks in cases:
        rng = np.random.default_rng(SEED)
        q, k, v = (jnp.asarray(rng.standard_normal(shape), dtype)
                   for _ in range(3))

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, interpret=False,
                                   **blocks)

        def naive(q, k, v):
            return naive_attention(q, k, v, causal=True)

        def grads(fn):
            return jax.grad(lambda q, k, v: fn(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

        out, ref = flash(q, k, v), naive(q, k, v)
        pairs = [("out", out, ref)] + [
            (f"d{n}", a, b) for n, a, b in
            zip("qkv", grads(flash), grads(naive))]
        for what, got, want in pairs:
            got = np.asarray(got, np.float32)
            want = np.asarray(want, np.float32)
            assert np.isfinite(got).all(), f"{name} {what}: non-finite"
            np.testing.assert_allclose(
                got, want, atol=2e-2, rtol=2e-2,
                err_msg=f"flash vs naive, {name}, {what}")
        err = float(np.abs(np.asarray(out, np.float32)
                           - np.asarray(ref, np.float32)).max())
        say(f"kernel {name}: forward + grad agree with naive_attention "
            f"(max |out err| {err:.2e})")


# --- the platform, through its public surface -------------------------


def make_data(workdir: str, spec: dict):
    """A learnable Markov stream from a fixed seed; the val stream holds
    exactly four evaluation windows."""
    from rafiki_tpu.datasets import make_synthetic_token_dataset

    return make_synthetic_token_dataset(
        os.path.join(workdir, "data"), n_train=1 << 18,
        n_val=4 * spec["seq_len"] + 1, vocab_size=spec["vocab_size"],
        seed=SEED)


def start_platform(workdir: str, n_chips: int, **node_overrides):
    """Compose the node as ``python -m rafiki_tpu serve`` does: one
    validated NodeConfig, exported to env, then the platform with its
    HTTP admin. Returns (platform, logged-in model-developer client)."""
    from rafiki_tpu.client import Client
    from rafiki_tpu.config import NodeConfig
    from rafiki_tpu.constants import UserType
    from rafiki_tpu.platform import LocalPlatform

    cfg = NodeConfig.from_env(workdir=workdir, port=0, n_chips=n_chips,
                              **node_overrides)
    cfg.apply_env()
    platform = LocalPlatform.from_config(cfg, http=True)
    say(f"platform up: admin on port {platform.admin_port}, "
        f"{platform.allocator.n_chips} chip(s), bus "
        f"{type(platform.bus).__name__} (in-process)")
    root = Client("127.0.0.1", platform.admin_port, timeout=600)
    root.login("superadmin@rafiki", "rafiki")
    root.create_user("smoke@example.com", "pw", UserType.MODEL_DEVELOPER)
    dev = Client("127.0.0.1", platform.admin_port, timeout=600)
    dev.login("smoke@example.com", "pw")
    return platform, dev


def register_model(dev, spec: dict) -> str:
    from rafiki_tpu.constants import TaskType

    source = MODEL_SOURCE % {"knobs": spec, "seed": SEED}
    model = dev.create_model("flagship-lm", TaskType.LANGUAGE_MODELING,
                             "FlagshipLM", model_source=source)
    return model["id"]


def train_one_trial(dev, model_id: str, data, spec: dict, app: str,
                    **budget):
    """create_train_job (one trial) -> wait -> the trial row is COMPLETED
    (a crashed trial is ERRORED while its job still STOPs cleanly), every
    logged loss finite and the last below the first. Returns
    (train_job_id, losses, the chips its train service was given)."""
    import math

    from rafiki_tpu.constants import BudgetOption, TaskType

    t0 = time.monotonic()
    before = {s["id"] for s in dev.get_services()}
    job = dev.create_train_job(
        app, TaskType.LANGUAGE_MODELING, [model_id],
        {BudgetOption.MODEL_TRIAL_COUNT: 1, **budget}, data[0], data[1])
    done = dev.wait_until_train_job_done(job["id"], timeout=1500)
    wall = time.monotonic() - t0
    assert done["status"] == "STOPPED", done
    trials = dev.get_trials_of_train_job(job["id"])
    assert len(trials) == 1, trials
    trial = trials[0]
    logs = [row["record"] for row in dev.get_trial_logs(trial["id"])]
    assert trial["status"] == "COMPLETED", \
        f"trial {trial['id']} is {trial['status']}:\n{logs[-3:]}"
    records = [r.get("values") or {} for r in logs]
    losses = [float(v["loss"]) for v in records if "loss" in v]
    steps = max(int(v["step"]) for v in records if "step" in v)
    assert steps == spec["train_steps"], (steps, spec["train_steps"])
    assert len(losses) >= 2 and all(math.isfinite(x) for x in losses), \
        losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    chips = sorted(tuple(s["chips"]) for s in dev.get_services()
                   if s["service_type"] == "TRAIN"
                   and s["id"] not in before)
    tokens = steps * spec["batch_size"] * spec["seq_len"]
    say(f"trial {trial['id'][:8]} COMPLETED on chips {chips}: "
        f"{steps} steps, loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
        f"score {trial['score']:.4f}; job wall {wall:.1f}s incl. compile, "
        f"evaluate and param save = {steps / wall:.2f} steps/s, "
        f"{tokens / wall:.0f} tokens/s (smoke figures)")
    return job["id"], losses, chips


def deploy(dev, train_job_id: str):
    """create_inference_job -> (inference job id, predictor host)."""
    inf = dev.create_inference_job(train_job_id, max_models=1)
    host = dev.get_inference_job(inf["id"])["predictor_host"]
    assert host, inf
    return inf["id"], host


def worker_registrations(platform, inference_job_id: str) -> dict:
    from rafiki_tpu.cache import Cache

    return Cache(platform.bus).running_worker_info(inference_job_id)


def wait_for_workers(host: str, n: int, timeout: float = 600.0) -> None:
    import requests

    deadline = time.monotonic() + timeout
    while True:
        seen = requests.get(f"http://{host}/", timeout=30).json()
        if seen["n_workers"] >= n:
            return
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"{seen['n_workers']}/{n} workers after {timeout}s")
        time.sleep(0.5)


def scoring_queries(val_path: str, spec: dict):
    """Token-id sequences cut from the val stream: short, medium, full."""
    from rafiki_tpu.model.dataset import load_token_dataset

    ids = load_token_dataset(val_path).ids
    t = spec["seq_len"]
    lengths = [t // 16 + 1, t // 4 + 1, t // 2 + 1, t + 1]
    return [ids[i * 7:i * 7 + n].tolist()
            for i, n in enumerate(lengths * 2)]


def score(dev, host: str, queries) -> list:
    """POST /predict; every answer a finite mean log-probability."""
    import math

    preds = dev.predict(host, queries=queries)["predictions"]
    assert len(preds) == len(queries), (len(preds), len(queries))
    for p in preds:
        assert isinstance(p, float) and math.isfinite(p) and p < 0.0, preds
    return preds


def generate(host: str, tokens, max_new: int) -> list:
    """POST /generate, greedy; returns the streamed tokens. The stream
    must end in a ``done`` frame with a real finish reason."""
    import requests

    out, last = [], None
    with requests.post(f"http://{host}/generate",
                       json={"tokens": tokens, "max_new": max_new,
                             "temperature": 0.0},
                       stream=True, timeout=600) as resp:
        assert resp.status_code == 200, (resp.status_code, resp.text)
        for line in resp.iter_lines():
            if not line:
                continue
            last = json.loads(line)
            out.extend(last.get("tok", ()))
    assert last and last.get("done") and \
        last.get("finish") in ("length", "eos"), last
    assert last["n_tokens"] == len(out) == max_new, (last, out)
    return out


# --- one chip ----------------------------------------------------------


def train_step_text(spec: dict, params) -> str:
    """Compiled text of the train step the trial ran, recovered from the
    in-memory step cache and lowered at the trial's own shapes."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.model import jax_model

    entries = [e for key, e in jax_model._STEP_CACHE.items()
               if key[1] == "train" and key[0].__name__ == "FlagshipLM"]
    assert len(entries) == 1, f"{len(entries)} cached FlagshipLM steps"

    def like(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

    params = jax.tree.map(like, params)
    rep = jax.tree.leaves(params)[0].sharding
    opt = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(entries[0]["init_opt"], params))
    wins = jax.ShapeDtypeStruct(
        (8, spec["batch_size"], spec["seq_len"] + 1), jnp.int32,
        sharding=rep)
    return entries[0]["step"].lower(params, opt, wins).compile().as_text()


def one_chip(spec: dict, workdir: str) -> None:
    import jax
    import numpy as np

    chip = jax.devices()[0]
    with phase("kernel numerics (compiled flash vs naive)"):
        check_kernel()

    with phase("platform start + data"):
        data = make_data(workdir, spec)
        # Generative serving on; the pool holds every decode lane at
        # full length (pages_per_seq = (2048 + 32) / 16 = 130).
        platform, dev = start_platform(
            workdir, n_chips=1, serving_generate=True,
            generate_decode_batch=4, generate_max_new=32,
            generate_pool_pages=4 * 130 + 1)
    try:
        with phase("train: create_train_job, one flagship trial"):
            model_id = register_model(dev, spec)
            job_id, _, chips = train_one_trial(
                dev, model_id, data, spec, "chip-smoke")
            assert chips == [(0,)], chips
            peak = peak_bytes(chip)
            say(f"{chip}: peak_bytes_in_use {peak / 1e9:.2f} GB")
            assert peak > F32_WEIGHT_BYTES, peak

        with phase("deploy: create_inference_job"):
            inf_id, host = deploy(dev, job_id)
            wait_for_workers(host, 1)
            (worker_id, reg), = worker_registrations(
                platform, inf_id).items()
            say(f"worker {worker_id[:8]} registered: "
                f"serving_pipeline auto -> {reg['pipeline']} "
                f"(sync {reg['sync_latency_ms']} ms), "
                f"staging={reg['staging']}, gen={reg['gen']}")
            assert reg["gen"], "the worker serves no generation"
            assert reg["staging"] in ("pinned", "pageable"), reg

        with phase("on-device residency + compiled kernel"):
            worker = platform.container.get(worker_id)
            params = worker._model._params
            for leaf in jax.tree.leaves(params):
                assert leaf.devices() == {chip}, leaf.devices()
            text = train_step_text(spec, params)
            assert "tpu_custom_call" in text, \
                "no Mosaic kernel in the compiled train step"
            say(f"served params live on {chip}; the train step holds "
                f"{text.count('tpu_custom_call')} tpu_custom_call sites")

        with phase("POST /predict: scoring requests"):
            queries = scoring_queries(data[1], spec)
            single = dev.predict(host, query=queries[0])["prediction"]
            batch = score(dev, host, queries)
            assert abs(single - batch[0]) < 1e-4, (single, batch[0])
            say(f"mean log-probs: {[round(p, 3) for p in batch]}")

        with phase("POST /generate: overlapping greedy streams"):
            prompt_a, prompt_b = queries[0][:48], queries[1][:33]
            with ThreadPoolExecutor(3) as pool:
                streams = [pool.submit(generate, host, p, 16)
                           for p in (prompt_a, prompt_b, prompt_a)]
                first_a, out_b, second_a = (f.result() for f in streams)
            assert first_a == second_a, (first_a, second_a)
            # Sequentially again: served from the prefix cache, same
            # tokens.
            assert generate(host, prompt_a, 16) == first_a
            # The decode path's greedy choice must be what the scoring
            # path (full forward on the flash kernel) rates above a
            # random continuation of the same prompt.
            noise = np.random.default_rng(SEED).integers(
                0, spec["vocab_size"], size=16).tolist()
            greedy, random_ = score(
                dev, host, [prompt_a + first_a, prompt_a + noise])
            assert greedy > random_, (greedy, random_)
            say(f"3 overlapping streams + 1 cached repeat done; greedy "
                f"continuation scores {greedy:.3f} vs random "
                f"{random_:.3f}")

        with phase("stop inference job"):
            dev.stop_inference_job(inf_id)
    finally:
        platform.shutdown()


# --- four chips --------------------------------------------------------


def replica_reply_counts(dev_port: int) -> dict:
    """Replies gathered per replica worker, from the admin's /metrics."""
    import requests

    from rafiki_tpu.observe.metrics import parse_exposition

    text = requests.get(f"http://127.0.0.1:{dev_port}/metrics",
                        timeout=30).text
    series = parse_exposition(text).get(
        "rafiki_tpu_serving_replica_gather_seconds_count", [])
    return {labels["worker"]: int(v) for labels, v in series}


def four_chips(spec: dict, workdir: str) -> None:
    import jax
    import numpy as np

    from rafiki_tpu.admin.services_manager import CHIPS_PER_TRIAL
    from rafiki_tpu.constants import BudgetOption

    chips = jax.devices()[:4]
    assert len(chips) == 4, f"--chips 4 needs four chips, found {chips}"

    with phase("platform start + data"):
        data = make_data(workdir, spec)
        platform, dev = start_platform(workdir, n_chips=4)
    try:
        model_id = register_model(dev, spec)

        with phase("train on a one-chip group that is not device 0"):
            # Reserve chip 0 so the allocator places the trial elsewhere:
            # whatever then shows up on device 0 was staged through it.
            held = platform.allocator.allocate(1, "chip_smoke:hold-0")
            assert held.indices == (0,), held
            job1, loss1, placed = train_one_trial(
                dev, model_id, data, spec, "chip-smoke-dp1")
            platform.allocator.release("chip_smoke:hold-0")
            (k,), = placed
            assert k != 0, placed
            peaks = [peak_bytes(d) for d in chips]
            say("peak_bytes_in_use GB: "
                + ", ".join(f"{p / 1e9:.2f}" for p in peaks))
            assert peaks[k] > F32_WEIGHT_BYTES, peaks
            assert peaks[0] < 64 << 20, \
                f"device 0 held {peaks[0]} bytes of a trial on chip {k}"

        with phase("train the same trial on a four-chip group (dp=4)"):
            _, loss4, placed = train_one_trial(
                dev, model_id, data, spec, "chip-smoke-dp4",
                **{BudgetOption.CHIP_COUNT: 4, CHIPS_PER_TRIAL: 4})
            assert sorted(placed[0]) == [0, 1, 2, 3], placed
            np.testing.assert_allclose(
                loss4, loss1, rtol=DP_LOSS_RTOL,
                err_msg="dp=4 vs one chip, same seed and global batch")
            say(f"final loss dp=4 {loss4[-1]:.4f} vs one chip "
                f"{loss1[-1]:.4f} (rel diff "
                f"{abs(loss4[-1] - loss1[-1]) / loss1[-1]:.1e}, "
                f"tolerance {DP_LOSS_RTOL})")
            peaks = [peak_bytes(d) for d in chips]
            say("peak_bytes_in_use GB: "
                + ", ".join(f"{p / 1e9:.2f}" for p in peaks))
            # Replicated state: every chip of the group holds its own
            # copy of the weights, and device 0 no more than its peers.
            assert min(peaks) > F32_WEIGHT_BYTES, peaks
            assert peaks[0] <= 1.05 * float(np.median(peaks[1:])), peaks

        with phase("deploy the one-chip trial: one replica"):
            inf_id, host = deploy(dev, job1)
            wait_for_workers(host, 1)
            queries = scoring_queries(data[1], spec)
            alone = score(dev, host, queries)

        with phase("widen to four one-chip replicas"):
            for _ in range(3):
                attached = platform.admin.attach_inference_workers(inf_id)
                assert len(attached) == 1, attached
            wait_for_workers(host, 4)
            replica_chips = sorted(
                tuple(s["chips"]) for s in dev.get_services()
                if s["service_type"] == "INFERENCE"
                and s["status"] == "RUNNING" and s.get("chips"))
            assert replica_chips == [(0,), (1,), (2,), (3,)], replica_chips
            workers = {w[:8] for w in worker_registrations(platform, inf_id)}
            # Every replica compiles its own scoring program on its first
            # shard; ask until each has answered some.
            for round_ in range(6):
                together = score(dev, host, queries)
                counts = replica_reply_counts(platform.admin_port)
                if all(counts.get(w, 0) > 0 for w in workers):
                    break
            say(f"replies per replica after {round_ + 1} rounds: "
                f"{ {w: counts.get(w, 0) for w in sorted(workers)} }")
            assert all(counts.get(w, 0) > 0 for w in workers), counts
            np.testing.assert_allclose(
                together, alone, rtol=0, atol=1e-4,
                err_msg="four replicas vs the single replica")
            peaks = [peak_bytes(d) for d in chips]
            say("peak_bytes_in_use GB: "
                + ", ".join(f"{p / 1e9:.2f}" for p in peaks))
            assert peaks[0] <= 1.05 * max(peaks[1:]), peaks

        with phase("stop inference job"):
            dev.stop_inference_job(inf_id)
    finally:
        platform.shutdown()


# --- entry --------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Drive train -> deploy -> predict/generate once on the "
                    "attached TPU and print one JSON result line.")
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): the whole one-chip path. 4: only the four-chip "
             "path (dp=4 trial, four replicas) and what it is compared "
             "with.")
    args = parser.parse_args(argv)

    t0 = time.monotonic()
    device = device_report()
    counters = CompileCounters()
    if device["count"] < args.chips:
        raise SystemExit(f"--chips {args.chips} but jax sees "
                         f"{device['count']} device(s)")
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        (four_chips if args.chips == 4 else one_chip)(FLAGSHIP, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    counters.report()
    say(f"[phase] total: {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
