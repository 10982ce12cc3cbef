"""The comparison that decides ``correct`` for a trained trial: what the
timed path produced (a completed trial's logged losses and its
parameters read back from the param store) against the plain
reference's run of the same trial.

Numbers (each has a limit of its own in the workload file):

``loss_gap``
    The widest relative gap between a logged loss and the reference's:
    the program logs the mean loss of every dispatch of
    ``steps_per_dispatch`` optimizer steps, the reference's per-step
    losses are averaged over the same steps.
``dparam_gap``
    Worst leaf of | ||p_prog - p0|| - ||p_ref - p0|| |, the gap between
    the two norms of the parameters' change over the trial, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. A leaf is one layer's slice of a stacked
    matrix. p0 is the reference's own initial state: a program that
    starts elsewhere, leaves a leaf unmoved or moves it double reads
    about 1.
``bad_trials``
    Trials completed in the window whose logs are not ``train_steps``
    steps of finite losses. Limit 0.
"""

from __future__ import annotations

import math

import numpy as np


def chunk_means(step_losses, per_dispatch: int):
    n = len(step_losses)
    return [float(np.mean(step_losses[i:i + per_dispatch]))
            for i in range(0, n, per_dispatch)]


def loss_gap(logged, reference) -> float:
    if len(logged) != len(reference) or not logged:
        return math.inf
    gaps = [abs(a - b) / abs(b) for a, b in zip(logged, reference)]
    return float(max(gaps)) if all(map(math.isfinite, gaps)) else math.inf


def _leaves(params: dict, stacked: int):
    """name -> array, stacked matrices cut into their layers."""
    out = {}
    for name, value in sorted(params.items()):
        value = np.asarray(value, np.float64)
        if value.ndim >= 2 and value.shape[0] == stacked:
            for i in range(stacked):
                out[f"{name}[{i}]"] = value[i]
        else:
            out[name] = value
    return out


def dparam_gap(program: dict, reference: dict, initial: dict,
               layers: int):
    """(worst gap, the leaf it was read on)."""
    prog, ref, first = (_leaves(p, layers)
                        for p in (program, reference, initial))
    if set(prog) != set(ref):
        return math.inf, "leaf names differ"
    ref_norm = {k: float(np.linalg.norm(ref[k] - first[k])) for k in ref}
    median = float(np.median(list(ref_norm.values())))
    worst, where = 0.0, ""
    for k in sorted(ref):
        if prog[k].shape != ref[k].shape \
                or not np.isfinite(prog[k]).all():
            return math.inf, k
        mine = float(np.linalg.norm(prog[k] - first[k]))
        gap = abs(mine - ref_norm[k]) / max(ref_norm[k], median, 1e-30)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def bad_trials(trials, steps: int) -> int:
    """``trials``: one list of (step, loss) per completed trial."""
    bad = 0
    for rows in trials:
        ok = bool(rows) and max(s for s, _ in rows) == steps \
            and all(math.isfinite(x) for _, x in rows)
        bad += not ok
    return bad
