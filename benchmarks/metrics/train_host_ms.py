"""Train worker: the part of a trial's train phase in which the host is
not waiting for the device, in milliseconds: set-up, cutting and
shipping windows, dispatch, and the trace and compile of a step that
missed the cache. Growth of (``train`` - ``step_wait``) between the
window's edges / trials (propose_ms.py has the arithmetic)."""

from harness import load_module


def read(run):
    per_trial_ms = load_module("metrics", "propose_ms").per_trial_ms
    return per_trial_ms(run, "train", minus=("step_wait",))
