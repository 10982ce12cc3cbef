"""Expert layer of the LFM2-family hybrid LM: load imbalance of the held
experts over the whole run, from the program's counters
(``rafiki_tpu_moe_assignments_total``,
``rafiki_tpu_moe_busiest_expert_total``): moe_load_imbalance.py's
quantity and its code, under this cell's name."""

from harness import load_module


def read(run):
    if "layer_types" not in run["knobs"]:
        return None
    return load_module("metrics", "moe_load_imbalance").read(run)
