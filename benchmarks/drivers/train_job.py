"""The train-job driver: the entry the window drives is
``Client.create_train_job`` over the HTTP admin of an in-process
``LocalPlatform`` (one process holds the chip), down through Admin,
ServicesManager, TrainWorker, TrialRunner, the advisor, the model's
train / evaluate / dump_parameters, ParamStore and the meta store.

Set-up: start the platform, make the data from ``--seed``, upload the
configuration's template rendered with the workload's knobs, create ONE
train job with far more trials than the window can hold. The job's first
trial is the warm-up: it pays the compiles or the cache loads.

Window: opens at the instant the first trial is COMPLETED and closes at
the first trial completion at or after ``--seconds`` later, both taken
from the trial rows' own ``finished_at`` (the meta store's ``time.time()``
floats; the client's view of a trial carries no instants, so the rows
are read from the platform object this process holds). Work is the trials completed
inside, time the window's real length: a steady state by construction,
each trial's pipelined persist tail overlapping the next as in a long
job. The job is then stopped; the trial cut short by the stop is neither
attempted nor failed.

With ``--trace 1`` the profiler runs over the window's first whole trial
cycle (completion to completion).

``correct``: once the window has closed, the peak is read and the
platform is shut down, the plain reference follows one trial completed
in the window, drawn from the seed, and is compared with that trial's
logged losses and its parameters read back from the param store
(``compare.py``: every number it gives is printed under ``compared``,
and ``compare.judge`` holds those the workload's file limits).

Everything of one cell comes from its workload and configuration files;
this file names none.
"""

from __future__ import annotations

import gc
import os
import random
import shutil
import sys
import time

from harness import ROOT, load_module, read_text

POLL_S = 0.2  # only to notice a completion; the instants are the rows'
TERMINAL = ("COMPLETED", "ERRORED", "TERMINATED")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


class CompileLog:
    """jax's own monitoring events with the host's clock beside them:
    (wall time at the end, program, seconds) of every backend compile or
    retrieval from the persistent cache."""

    def __init__(self):
        import jax.monitoring

        self.compiles = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, seconds, **kw):
        if event == COMPILE_EVENT:
            self.compiles.append(
                (time.time(), str(kw.get("fun_name")), float(seconds)))


def resolve_device(chips: int, selftest: bool):
    """The platform as the program resolves it; no TPU, an unknown kind
    or too few chips ends the run with no result."""
    from rafiki_tpu.jaxenv import ensure_platform

    platform = ensure_platform()
    if platform != "tpu" and not selftest:
        raise SystemExit(f"a measurement run needs the TPU, not "
                         f"{platform!r}")
    import jax

    import flops

    devices = jax.devices()
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), jax sees "
                         f"{len(devices)}")
    peaks = flops.load_peaks(devices[0].device_kind) \
        if platform == "tpu" else None
    return devices, peaks


def start_platform(workdir: str, chips: int):
    """Compose the node as ``python -m rafiki_tpu serve`` does and log a
    model developer in (as chip_smoke.py:start_platform)."""
    from rafiki_tpu.client import Client
    from rafiki_tpu.config import NodeConfig
    from rafiki_tpu.constants import UserType
    from rafiki_tpu.platform import LocalPlatform

    cfg = NodeConfig.from_env(workdir=workdir, port=0, n_chips=chips)
    cfg.apply_env()
    platform = LocalPlatform.from_config(cfg, http=True)
    root = Client("127.0.0.1", platform.admin_port, timeout=600)
    root.login("superadmin@rafiki", "rafiki")
    root.create_user("bench@example.com", "pw", UserType.MODEL_DEVELOPER)
    dev = Client("127.0.0.1", platform.admin_port, timeout=600)
    dev.login("bench@example.com", "pw")
    return platform, dev


def searched(spec: dict, seed: int) -> dict:
    """A searched knob as the template gets it. ``jitter`` (a share, in
    the workload's file) scales the knob's numeric arguments by one
    factor in [1, 1 + jitter) drawn from the seed: the program's advisor
    draws its first proposals from a seed of its own that is the same in
    every job, so without it every run would search the very same
    points and find their compiled steps in the persistent cache, as no
    user's search does."""
    spec = dict(spec)
    jitter = float(spec.pop("jitter", 0.0))
    if jitter:
        factor = 1.0 + jitter * random.Random(seed).random()
        spec["args"] = [a * factor if isinstance(a, float) else a
                        for a in spec["args"]]
    return spec


def render_template(config: dict, workload: dict, seed: int) -> str:
    fixed = {knob: config[key] for knob, key in config["knob_of"].items()}
    fixed.update(config["knobs"])
    fixed.update(workload["job"]["fixed"])
    fixed["seed"] = seed
    search = {name: searched(spec, seed)
              for name, spec in workload["job"]["search"].items()}
    return read_text("templates", config["template"] + ".py.tmpl") % {
        "model_class": config["model_class"], "fixed": fixed,
        "search": search}


def trial_log(dev, trial_id: str):
    """((step, loss) of every logged dispatch of one trial, the host's
    instants of the trial's log records: the first is written as the
    train loop begins, the others as each dispatch ends)."""
    rows, instants = [], []
    for row in dev.get_trial_logs(trial_id):
        record = row.get("record") or {}
        values = record.get("values") or {}
        if "time" in record:
            instants.append(float(record["time"]))
        if "step" in values and "loss" in values:
            rows.append((int(values["step"]), float(values["loss"])))
    return rows, instants


def host_spans(trials, logs, compiles, zero: float):
    """What the host was doing, from what the benchmark itself records,
    as (label, start_ns, end_ns) on the trace's clock, which starts at
    ``zero``: every compile or cache retrieval; then, of each trial, its
    tail (last dispatch logged to the row's completion: evaluation,
    parameter dump, hand-over to the persist tail), its head (row made
    to train loop begun: load, stage, init) and its train loop; then
    what lies between two trials (advisor)."""
    def ns(t):
        return (t - zero) * 1e9

    spans = [(f"compile or cache load: {name}", ns(t - seconds), ns(t))
             for t, name, seconds in compiles]
    previous = None
    for trial in sorted(trials, key=lambda t: t["started_at"]):
        instants = logs.get(trial["id"], ((), ()))[1]
        start, end = trial["started_at"], trial["finished_at"]
        if instants:
            spans.append(("trial tail: eval, dump, hand-over",
                          ns(max(instants)), ns(end)))
            spans.append(("trial head: load, stage, init",
                          ns(start), ns(min(instants))))
            spans.append(("train loop: between dispatches",
                          ns(min(instants)), ns(max(instants))))
        if previous is not None:
            spans.append(("between trials: feedback, propose",
                          ns(previous), ns(start)))
        previous = end
    return spans


def peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


def run(*, args, workload, config, t_start, selftest):
    import numpy as np

    chips = int(workload["chips"])
    devices, peaks = resolve_device(chips, selftest)
    devices = devices[:chips]
    import jax

    import compare
    import flops
    import trace_reduce
    from rafiki_tpu.constants import BudgetOption
    from rafiki_tpu.observe import phases

    compile_log = CompileLog()
    # Weights and windows take the seed as an int32.
    seed = int(args.seed) % 2147483647
    workdir = os.path.join(ROOT, ".bench_work", workload["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)

    data = load_module("data", config["data"]["generator"])
    train_path, val_path, train_ids = data.make(
        os.path.join(workdir, "data"), seed, config["data"], config)
    platform, dev = start_platform(workdir, chips)
    say(f"platform up on port {platform.admin_port}, {chips} chip(s), "
        f"workdir {workdir}")

    model = dev.create_model(
        workload["name"], config["task"], config["model_class"],
        model_source=render_template(config, workload, seed))
    budget = dict(workload["job"]["budget"])
    budget[BudgetOption.CHIP_COUNT] = chips
    job = dev.create_train_job(workload["name"], config["task"],
                               [model["id"]], budget, train_path, val_path)

    # --- warm-up trial, then the window -------------------------------
    t_open = t_close = None
    phase_open = phase_close = None
    trace = None           # {"dir", "t0", "t1"} once started
    deadline = time.time() + 1100
    seen = {}
    while t_close is None:
        time.sleep(POLL_S)
        trials = platform.meta.get_trials_of_train_job(job["id"])
        done = sorted((t for t in trials if t["status"] in TERMINAL
                       and t.get("finished_at")),
                      key=lambda t: t["finished_at"])
        fresh = [t for t in done if t["id"] not in seen]
        for t in fresh:
            seen[t["id"]] = t
            say(f"trial #{t['no']} {t['status']} at "
                f"+{t['finished_at'] - t_start:.2f}s score {t['score']}")
        if t_open is None:
            if not done:
                if time.time() > deadline:
                    raise SystemExit("the warm-up trial never ended")
                continue
            if done[0]["status"] != "COMPLETED":
                raise SystemExit(f"the warm-up trial ended "
                                 f"{done[0]['status']}: {done[0]['error']}")
            t_open = done[0]["finished_at"]
            phase_open = phases.phase_totals()
            if args.trace and devices[0].platform != "cpu":
                trace = {"dir": os.path.join(workdir, "trace")}
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                trace["t0"] = time.time()  # the trace's clock starts here
                jax.profiler.start_trace(trace["dir"],
                                         profiler_options=options)
            continue
        inside = [t for t in done if t["finished_at"] > t_open]
        if trace is not None and "t1" not in trace and inside:
            trace["t1"] = time.time()
            jax.profiler.stop_trace()
            say(f"traced {trace['t1'] - trace['t0']:.2f}s, stop took "
                f"{time.time() - trace['t1']:.2f}s")
        closing = [t for t in inside if t["status"] == "COMPLETED"
                   and t["finished_at"] >= t_open + args.seconds]
        if closing:
            t_close = closing[0]["finished_at"]
        elif platform.meta.get_train_job(job["id"])["status"] in (
                "STOPPED", "ERRORED") \
                or time.time() > t_open + args.seconds + 300:
            # The workers gave up, or nothing completes any more: close
            # on the last trial that ended, so that its errors count.
            if not inside:
                raise SystemExit("no trial ended inside the window")
            t_close = inside[-1]["finished_at"]
    phase_close = phases.phase_totals()
    inside = [t for t in seen.values() if t_open < t["finished_at"] <= t_close]
    completed = sorted((t for t in inside if t["status"] == "COMPLETED"),
                       key=lambda t: t["finished_at"])
    failed = [t for t in inside if t["status"] != "COMPLETED"]
    memory_peak = peak_bytes(devices)
    say(f"window {t_close - t_open:.2f}s: {len(completed)} completed, "
        f"{len(failed)} failed; peak {memory_peak / 1e9:.2f} GB")
    if not completed:
        raise SystemExit("no trial completed inside the window")

    # --- stop the job; wait for the trial it cut short ----------------
    dev.stop_train_job(job["id"])
    wait_until = time.time() + 180
    while time.time() < wait_until:
        rows = platform.meta.get_trials_of_train_job(job["id"])
        if all(t["status"] in TERMINAL for t in rows):
            break
        time.sleep(POLL_S)
    logs = {t["id"]: trial_log(dev, t["id"]) for t in seen.values()
            if t["status"] == "COMPLETED"}
    # One of the window's trials, drawn from the seed: over the seeds
    # the comparison meets every knob value the job's search proposes.
    checked = random.Random(seed).choice(completed)
    say(f"checking trial #{checked['no']}, knobs {checked['knobs']}")
    program_params = {name.split("/", 1)[-1]: np.asarray(value)
                      for name, value in
                      platform.params.load(checked["params_id"]).items()}
    platform.shutdown()
    del platform, dev
    gc.collect()
    jax.clear_caches()
    shutil.rmtree(os.path.join(workdir, "params"), ignore_errors=True)

    # --- the record the metric readers get ----------------------------
    knobs = checked["knobs"]
    shapes = flops.shapes_of(config)
    record = {
        "setup_s": t_open - t_start,
        "window": {"t0": t_open, "t1": t_close,
                   "seconds": t_close - t_open, "trials": len(completed),
                   "steps": sum(int(t["knobs"]["train_steps"])
                                for t in completed)},
        "phase_open": phase_open, "phase_close": phase_close,
        "compiles": compile_log.compiles,
        "shapes": shapes, "knobs": knobs, "chips": chips, "peaks": peaks,
        "trace": None,
        "attempted": len(inside), "failed": len(failed),
    }
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    if trace is not None:
        reduced = trace_reduce.reduce_dir(trace["dir"], chips)
        reduced["window_s"] = trace["t1"] - trace["t0"]
        record["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        record["breakdown"] = trace_reduce.breakdown(
            reduced, host_spans(seen.values(), logs, compile_log.compiles,
                                trace["t0"]))
    record["device"] = device

    # --- correct: the reference follows the checked trial --------------
    t_ref = time.time()
    reference = load_module("reference", config["reference"])
    dims = reference.dims_of(config)
    steps = int(knobs["train_steps"])
    per_dispatch = int(knobs["steps_per_dispatch"])
    first, final, step_losses = reference.train(
        train_ids, seed, dims, config["recipe"], steps=steps,
        batch=int(knobs["batch_size"]), per_dispatch=per_dispatch,
        learning_rate=float(knobs["learning_rate"]),
        host_dtype=np.float32)
    say(f"reference followed {steps} steps in {time.time() - t_ref:.1f}s")
    numbers = compare.trial_numbers(
        [x for _, x in logs[checked["id"]][0]], step_losses, per_dispatch,
        program_params, final, first, dims["layers"],
        **compare.kinds_of(reference))
    numbers["bad_trials"] = {"value": compare.bad_trials(
        [logs[t["id"]][0] for t in completed], steps)}
    record["compared"], record["correct"] = compare.judge(
        numbers, compare.limits_of(workload))
    shutil.rmtree(workdir, ignore_errors=True)
    return record
