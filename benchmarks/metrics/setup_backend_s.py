"""Device: the TPU runtime's start, in seconds. The set-up phase
``backend_init`` (``rafiki_tpu/jaxenv.py:ensure_platform``'s first
resolve: the compile cache placed, ``jax.devices()``) as the program's
totals stood when the window opened (``record["phase_open"]``).

``at_open`` is shared by the other ``setup_*`` readers: the program
times each set-up phase with ``rafiki_tpu/observe/phases.py:span`` into
the histogram rafiki_tpu_trial_phase_seconds, and jax's compile stages
(the ``jit_*`` phases) with the listener ``phases.watch_jit`` installs."""


def at_open(run, *phases, seen=None):
    """The summed seconds of ``phases`` at the window's opening; None
    where the program has no such phase (a parent older than it), or
    where the phase ``seen`` (by default each of ``phases``) had not been
    observed: the span never ran, or the program's metrics were off."""
    totals = run.get("phase_open")
    if not totals or any(p not in totals for p in phases):
        return None
    if any(totals.get(p, {}).get("count", 0) < 1
           for p in ((seen,) if seen else phases)):
        return None
    return sum(totals[p]["sum"] for p in phases)


def read(run):
    return at_open(run, "backend_init")
