"""Train worker: what of a trial no span covers, in milliseconds: the
``trial`` phase (the whole of ``run_one``) less its direct children.
Small, or the other phase metrics leave something out. The two sides
are observed as their spans end, so the trial the window cuts counts
its early phases and not yet its whole. Growth between the window's
edges / trials (propose_ms.py has the arithmetic)."""

from harness import load_module

CHILDREN = ("propose", "open", "init", "train", "eval", "dump", "feedback",
            "handover")


def read(run):
    per_trial_ms = load_module("metrics", "propose_ms").per_trial_ms
    return per_trial_ms(run, "trial", minus=CHILDREN)
