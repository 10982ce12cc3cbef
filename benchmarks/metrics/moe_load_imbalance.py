"""Expert layer: load imbalance of the held experts over the whole run.
The busiest held expert's assignments, summed over sparse blocks and
train steps, x experts held / all held assignments: 1 when the held
experts get equal shares, experts_held when one gets everything.

The program counts in ``rafiki_tpu_moe_assignments_total{where=held|
absent}`` and ``rafiki_tpu_moe_busiest_expert_total`` (each train
dispatch adds its sums: observe/phases.py:moe_routed). The driver
snapshots only the trial phases at the window's edges, so this reader
takes the registry's cumulative totals itself, in the run's own
process: whole-run means,
warm-up trial included. ``held_per_step`` (the same totals over the
steps the run dispatched: ``step_wait``'s count x steps_per_dispatch)
is what moe_step_mfu, moe_expert_roofline and moe_route_ms take for the
traced slice too."""


def totals():
    """{"held", "absent", "busiest"} and the steps dispatched, or None
    where the program has no such counter or counted nothing."""
    from rafiki_tpu.observe import phases

    if not hasattr(phases, "moe_counts"):  # a program older than the counter
        return None
    counts = phases.moe_counts()
    dispatches = phases.phase_totals().get("step_wait", {}).get("count", 0)
    if not counts.get("held") or not dispatches:
        return None
    return dict(counts, dispatches=int(dispatches))


def held_per_step(run):
    found = totals()
    if found is None:
        return None
    return found["held"] / (found["dispatches"]
                            * int(run["knobs"]["steps_per_dispatch"]))


def read(run):
    found = totals()
    if found is None or "experts_held" not in run["knobs"]:
        return None
    return found["busiest"] * int(run["knobs"]["experts_held"]) \
        / found["held"]
