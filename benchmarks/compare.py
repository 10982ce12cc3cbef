"""The comparison that decides ``correct`` for a trained trial: what the
timed path produced (a completed trial's logged losses and its
parameters read back from the param store) against the plain
reference's run of the same trial.

A leaf is one layer's slice of a stacked matrix. p0 is the reference's
own initial state, p_ref its final one, p_prog the program's. Which
leaves are STATE (no gradient, no optimizer step) and which are ROUTED
(behind a top-k choice: the experts and their routers) the reference
says (its ``is_state`` and ``is_routed``; a reference without one has
none): this file names no leaf, no configuration and no cell.

``loss_gap``
    The widest relative gap between a logged loss and the reference's:
    the program logs the mean loss of every dispatch of
    ``steps_per_dispatch`` optimizer steps, the reference's per-step
    losses are averaged over the same steps. Beside it ``dispatches``,
    every dispatch's gap in order, and ``reference``, the reference's
    mean loss of each. A gross-fault number where the
    trajectory itself diverges: two sound runs of one trial part from
    dispatch to dispatch, on some seeds a hundred times further than on
    others.
``loss_gap_first``
    The first dispatch's gap alone: what the forward pass and the first
    optimizer steps do with the same data from the same state, before
    the trajectory's own divergence has grown. It holds the precision
    where ``loss_gap`` cannot: one precision step moves it several
    times and the seed hardly.
``dparam_gap``
    Worst leaf of | ||p_prog - p0|| - ||p_ref - p0|| |, the gap between
    the two NORMS of the parameters' change over the trial, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. A gross-fault number: a program that starts
    elsewhere, leaves a leaf unmoved or moves it double reads about 1;
    a precision step hardly moves it. State leaves are left out.
``update_gap``
    Median over the leaves that take a gradient of u = ||p_prog -
    p_ref|| / ||p_ref - p0||, the norm of the DIFFERENCE of the two
    updates: it keeps the update's direction, which ``dparam_gap``
    throws away. In a dense model one precision step moves it ten
    times; where a trajectory diverges of itself it reads what the
    divergence left, on some seeds as much as a precision step. Beside
    it ``worst`` and ``leaf``: the largest u and where (not judged: a
    router's layer reads several times the median).
``routed_gap``
    The same median of u over the routed leaves alone, where the
    reference names some. Flipped top-k choices give these leaves a
    floor of their own, several times the other leaves' u and steady
    from seed to seed, a diverging one included; a precision step
    lifts it by what it adds to the flips.
``state_gap``
    Worst state leaf of | ||b_prog - b0||_1 - ||b_ref - b0||_1 | over
    ||b_ref - b0||_1, the gap between the two 1-norms of the state's
    change: a state never updated reads 1. Beside it ``diff``, the
    worst leaf's ||b_prog - b_ref||_1 over the same (not judged: a
    diverging trajectory reads it at a third). Only where the
    reference names state.
``bad_trials``
    Trials completed in the window whose logs are not ``train_steps``
    steps of finite losses. Limit 0, always.

A leaf whose names or shapes differ, or that holds a non-finite value,
makes every number of the trees read ``inf``. One pass over the leaves
gives the tree numbers, one leaf at a time in float64: no tree is ever
copied whole, whatever type it is handed in.

``judge`` is the one rule that makes ``correct`` of numbers and limits;
the driver and both controls call it.
"""

from __future__ import annotations

import math

import numpy as np

#: Every number ``trial_numbers`` can give: the names a workload's file
#: may limit (``bad_trials`` is held to 0 without being named).
NUMBERS = ("loss_gap", "loss_gap_first", "dparam_gap", "update_gap",
           "routed_gap", "state_gap")


def chunk_means(step_losses, per_dispatch: int):
    n = len(step_losses)
    return [float(np.mean(step_losses[i:i + per_dispatch]))
            for i in range(0, n, per_dispatch)]


def loss_gaps(logged, reference):
    """The relative gap of every logged loss; None where the two logs
    differ in length, are empty or hold a gap that is no number."""
    if len(logged) != len(reference) or not logged:
        return None
    gaps = [float(abs(a - b) / abs(b)) for a, b in zip(logged, reference)]
    return gaps if all(map(math.isfinite, gaps)) else None


def loss_gap(logged, reference) -> float:
    gaps = loss_gaps(logged, reference)
    return max(gaps) if gaps else math.inf


def _cut(params: dict, stacked: int):
    """(leaf name, key, layer or None) of every leaf, stacked matrices
    cut into their layers. Shapes only: nothing is copied."""
    out = []
    for key in sorted(params):
        shape = np.shape(params[key])
        if len(shape) >= 2 and shape[0] == stacked:
            out += [(f"{key}[{i}]", key, i) for i in range(stacked)]
        else:
            out.append((key, key, None))
    return out


def _norm(x, state: bool) -> float:
    """The 1-norm of a state leaf's entries, the 2-norm of any other's."""
    return float(np.linalg.norm(x.ravel(), 1 if state else None))


def kinds_of(reference) -> dict:
    """What a reference module says of its leaves, as the keywords of
    ``leaf_rows``, ``tree_numbers`` and ``trial_numbers``."""
    return {name: getattr(reference, name, None)
            for name in ("is_state", "is_routed")}


def leaf_rows(program: dict, reference: dict, initial: dict, layers: int,
              is_state=None, is_routed=None):
    """One row a leaf: ``{"leaf", "state", "routed", "ref", "prog",
    "diff"}``, the norms of p_ref - p0, p_prog - p0 and p_prog - p_ref
    (2-norms; of a state leaf 1-norms). ``prog`` and ``diff`` are
    ``inf`` where the program's leaf has another shape or a non-finite
    value. None where the leaf names differ."""
    cut = _cut(reference, layers)
    if [name for name, _, _ in _cut(program, layers)] \
            != [name for name, _, _ in cut] or set(initial) != set(reference):
        return None
    rows = []
    for name, key, layer in cut:
        ref, first, prog = (
            np.asarray(p[key] if layer is None else p[key][layer],
                       np.float64) for p in (reference, initial, program))
        state = bool(is_state and is_state(key))
        sound = prog.shape == ref.shape and bool(np.isfinite(prog).all())
        rows.append({
            "leaf": name, "state": state,
            "routed": bool(is_routed and is_routed(key)),
            "ref": _norm(ref - first, state),
            "prog": _norm(prog - first, state) if sound else math.inf,
            "diff": _norm(prog - ref, state) if sound else math.inf})
    return rows


def _worst(pairs):
    """(largest value, its leaf) of (value, leaf) pairs; the first of
    equals, (0.0, "") of none."""
    worst, where = 0.0, ""
    for value, leaf in pairs:
        if value > worst:
            worst, where = value, leaf
    return worst, where


def numbers_of_rows(rows) -> dict:
    """The tree numbers of ``leaf_rows``' rows."""
    broken = "leaf names differ" if rows is None else next(
        (r["leaf"] for r in rows if not math.isfinite(r["diff"])), None)
    if broken is not None:
        out = {"dparam_gap": {"value": math.inf, "leaf": broken},
               "update_gap": {"value": math.inf, "worst": math.inf,
                              "leaf": broken}}
        if rows is None or any(r["routed"] for r in rows):
            out["routed_gap"] = {"value": math.inf}
        if rows is None or any(r["state"] for r in rows):
            out["state_gap"] = {"value": math.inf, "leaf": broken}
        return out
    weights = [r for r in rows if not r["state"]]
    state = [r for r in rows if r["state"]]
    median = float(np.median([r["ref"] for r in weights]))
    gap, where = _worst(
        (abs(r["prog"] - r["ref"]) / max(r["ref"], median, 1e-30),
         r["leaf"]) for r in weights)
    u = [(r["diff"] / max(r["ref"], 1e-30), r["leaf"], r["routed"])
         for r in weights]
    worst, worst_leaf = _worst((x, leaf) for x, leaf, _ in u)
    out = {"dparam_gap": {"value": gap, "leaf": where},
           "update_gap": {"value": float(np.median([x for x, _, _ in u])),
                          "worst": worst, "leaf": worst_leaf}}
    routed = [x for x, _, is_routed in u if is_routed]
    if routed:
        out["routed_gap"] = {"value": float(np.median(routed)),
                             "leaves": len(routed)}
    if state:
        value, leaf = _worst(
            (abs(r["prog"] - r["ref"]) / max(r["ref"], 1e-30), r["leaf"])
            for r in state)
        out["state_gap"] = {
            "value": value, "leaf": leaf,
            "diff": max(r["diff"] / max(r["ref"], 1e-30) for r in state)}
    return out


def tree_numbers(program: dict, reference: dict, initial: dict,
                 layers: int, is_state=None, is_routed=None) -> dict:
    """``dparam_gap``, ``update_gap``, where there are routed leaves
    ``routed_gap`` and where there are state leaves ``state_gap``, each
    ``{"value": ..., notes}``."""
    return numbers_of_rows(leaf_rows(program, reference, initial, layers,
                                     is_state, is_routed))


def dparam_gap(program: dict, reference: dict, initial: dict,
               layers: int, is_state=None):
    """(worst gap, the leaf it was read on)."""
    number = tree_numbers(program, reference, initial, layers,
                          is_state)["dparam_gap"]
    return number["value"], number["leaf"]


def trial_numbers(logged, step_losses, per_dispatch: int, program: dict,
                  reference: dict, initial: dict, layers: int,
                  is_state=None, is_routed=None) -> dict:
    """Every number of one trial against the reference's run of it:
    ``logged`` the losses the trial logged, a dispatch each,
    ``step_losses`` the reference's, a step each."""
    means = chunk_means(step_losses, per_dispatch)
    gaps = loss_gaps(list(logged), means)
    numbers = {"loss_gap": {"value": max(gaps) if gaps else math.inf,
                            "dispatches": gaps, "reference": means},
               "loss_gap_first": {"value": gaps[0] if gaps else math.inf},
               **tree_numbers(program, reference, initial, layers,
                              is_state, is_routed)}
    assert set(numbers) <= set(NUMBERS), list(numbers)
    return numbers


def bad_trials(trials, steps: int) -> int:
    """``trials``: one list of (step, loss) per completed trial."""
    bad = 0
    for rows in trials:
        ok = bool(rows) and max(s for s, _ in rows) == steps \
            and all(math.isfinite(x) for _, x in rows)
        bad += not ok
    return bad


def limits_of(workload: dict) -> dict:
    """A workload file's limits: ``limits``, and over them
    ``limits_more`` where a file has it (a test outside the benchmark
    pins the key set of one file's ``limits``: PERF.md section 7)."""
    return {**workload["limits"], **workload.get("limits_more", {})}


def judge(numbers: dict, limits: dict):
    """The one rule for ``correct``: every number that ``limits`` gives
    a limit has been read, is finite and is at or under it, and
    ``bad_trials``, wherever it is read, is 0. A number that ``limits``
    leaves out or gives ``None`` is shown with ``"limit": None`` and
    not judged. Returns (``compared``: name -> value, limit and the
    number's notes, ``correct``)."""
    limits = {name: limit for name, limit in limits.items()
              if limit is not None}
    if "bad_trials" in numbers:
        limits["bad_trials"] = 0
    compared = {name: {"value": number["value"],
                       "limit": limits.get(name),
                       **{k: v for k, v in number.items() if k != "value"}}
                for name, number in numbers.items()}
    correct = all(
        name in numbers and math.isfinite(numbers[name]["value"])
        and numbers[name]["value"] <= limit
        for name, limit in limits.items())
    return compared, bool(correct)
