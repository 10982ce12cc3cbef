"""Train worker: the persist tail of a trial (log flush, parameter save,
meta commit), in milliseconds. It runs on the persist thread and
overlaps the next trial, so it costs a trial only what
``handover_wait_ms`` shows. Growth of the ``persist`` phase's summed
seconds between the window's edges / trials (propose_ms.py has the
arithmetic)."""

from harness import load_module


def read(run):
    per_trial_ms = load_module("metrics", "propose_ms").per_trial_ms
    return per_trial_ms(run, "persist")
