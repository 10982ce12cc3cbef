"""Train worker: what tracing and lowering cost a trial inside the
window, in seconds: the growth of the phases ``jit_trace`` and
``jit_lower`` (jax's own events, ``phases.watch_jit``) between the
window's edges / trials completed in it. Where every trial builds its
own step, the part of the per-trial compile that is not XLA's. None
where the program has no such phases (a parent older than them) or
observed no trace at all (its metrics off)."""


def read(run):
    before, after = run.get("phase_open"), run.get("phase_close")
    trials = run["window"]["trials"]
    phases = ("jit_trace", "jit_lower")
    if not trials or not before or not after or any(
            p not in before or p not in after for p in phases) \
            or after["jit_trace"]["count"] < 1:
        return None
    return sum(after[p]["sum"] - before[p]["sum"] for p in phases) / trials
