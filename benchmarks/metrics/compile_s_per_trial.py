"""Train worker: seconds of backend compile (or of retrieval from the
persistent cache) that jax's monitoring events report inside the window,
per trial completed in it. An event is placed by the host's clock at its
end."""


def read(run):
    window = run["window"]
    if not window["trials"]:
        return None
    inside = sum(seconds for t, _, seconds in run["compiles"]
                 if window["t0"] < t <= window["t1"])
    return inside / window["trials"]
