"""Device: 1 - union of the device's op intervals / traced slice."""


def read(run):
    trace = run["trace"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
