"""Train worker: what a trial costs outside its train phase and is not
hidden by the pipeline. (window seconds - growth of the train phase's
summed seconds between the window's two edges) / trials. The phase sum
is the program's own histogram rafiki_tpu_trial_phase_seconds, read from
the in-process registry at both edges."""


def read(run):
    trials = run["window"]["trials"]
    if not trials or not run["phase_open"] or not run["phase_close"]:
        return None
    train = (run["phase_close"]["train"]["sum"]
             - run["phase_open"]["train"]["sum"])
    if train <= 0:
        return None
    return (run["window"]["seconds"] - train) / trials
