"""Model step: what ``model.evaluate`` costs a trial, in milliseconds, the
per-instance load of its forward program included. Growth of the
``eval`` phase's summed seconds between the window's edges / trials
(propose_ms.py has the arithmetic)."""

from harness import load_module


def read(run):
    per_trial_ms = load_module("metrics", "propose_ms").per_trial_ms
    return per_trial_ms(run, "eval")
