"""Advisor: what ``advisor.propose()`` costs a trial, in milliseconds.
Growth of the ``propose`` phase's summed seconds between the window's
two edges / trials completed in the window.

The program times every trial phase with one helper
(``rafiki_tpu/observe/phases.py:span``) into the histogram
rafiki_tpu_trial_phase_seconds; the driver reads its sums from the
in-process registry at both edges. ``per_trial_ms`` is shared by the
readers of the other phases (eval_ms, dump_ms, handover_wait_ms,
persist_ms, train_host_ms, trial_unattributed_ms)."""


def growth(run, phase: str):
    """Seconds the phase's sum grew by in the window; None where the
    program has no such phase (a parent older than the span) or
    observed none in the window."""
    before, after = run["phase_open"], run["phase_close"]
    if not before or not after or phase not in before \
            or phase not in after:
        return None
    if after[phase]["count"] <= before[phase]["count"]:
        return None
    return after[phase]["sum"] - before[phase]["sum"]


def per_trial_ms(run, phase: str, minus=()):
    """(growth of ``phase`` - growth of every phase in ``minus``) /
    trials, in milliseconds; None where any of them has nothing."""
    trials = run["window"]["trials"]
    seconds = [growth(run, name) for name in (phase, *minus)]
    if not trials or None in seconds:
        return None
    return 1e3 * (seconds[0] - sum(seconds[1:])) / trials


def read(run):
    return per_trial_ms(run, "propose")
