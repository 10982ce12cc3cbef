"""Model step: device time of one whole execution of the train program
in the traced slice / the optimizer steps it runs. The program is found
by name; each execution runs the template's ``steps_per_dispatch``
steps. The slice opens and closes at trial completions, which fall
inside the next trial's train loop (the persist tail is pipelined), so
it cuts into the execution in flight at each end: the trace holds those
two with the part of their time that it saw. A whole execution is the
median one."""

import statistics

PROGRAM = "train_chunk"


def executions(run):
    """(device seconds of a whole execution, executions in the slice
    with the cut ones counted by the share of a whole one that the
    trace saw), or None."""
    if not run["trace"]:
        return None
    durations = [seconds
                 for name, executed in run["trace"]["programs"].items()
                 if PROGRAM in name for seconds in executed]
    if not durations:
        return None
    whole = statistics.median(durations)
    return whole, sum(durations) / whole


def read(run):
    found = executions(run)
    if found is None:
        return None
    return 1e3 * found[0] / int(run["knobs"]["steps_per_dispatch"])
