"""Train worker: how long a finished trial waits to hand its tail to the
single-slot persist stage, in milliseconds a trial: the previous trial's
tail still writing. Time that work waited for a layer. Growth of the
``handover`` phase's summed seconds between the window's edges / trials
(propose_ms.py has the arithmetic)."""

from harness import load_module


def read(run):
    per_trial_ms = load_module("metrics", "propose_ms").per_trial_ms
    return per_trial_ms(run, "handover")
