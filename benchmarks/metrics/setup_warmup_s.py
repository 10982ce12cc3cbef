"""Train worker: the warm-up trial, in seconds: its ``trial`` span (the
whole of ``TrialRunner.run_one``) and its ``persist`` tail, in which the
row is completed, as the program's totals stood when the window opened.
None unless each phase had been observed exactly once then: that one
observation is the warm-up trial's. None too where the program times no
set-up (no ``worker_start``): there the tail began inside its trial's
span, and the two overlap."""

from harness import load_module

PHASES = ("trial", "persist")


def read(run):
    totals = run.get("phase_open")
    if not totals or "worker_start" not in totals or any(
            totals.get(p, {}).get("count") != 1 for p in PHASES):
        return None
    return load_module("metrics", "setup_backend_s").at_open(run, *PHASES)
