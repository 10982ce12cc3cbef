"""End to end: 3600 x trials completed in the window / the window's real
seconds (first completion to closing completion, the trial rows' own
clock). All the work over all the time."""


def read(run):
    window = run["window"]
    if not window["trials"] or window["seconds"] <= 0:
        return None
    return 3600.0 * window["trials"] / window["seconds"]
