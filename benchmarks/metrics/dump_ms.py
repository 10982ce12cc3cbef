"""Train worker: what ``model.dump_parameters()`` costs a trial on the
trial thread, in milliseconds: for the LM a synchronous device-to-host
copy of every leaf. Growth of the ``dump`` phase's summed seconds
between the window's edges / trials (propose_ms.py has the arithmetic)."""

from harness import load_module


def read(run):
    per_trial_ms = load_module("metrics", "propose_ms").per_trial_ms
    return per_trial_ms(run, "dump")
