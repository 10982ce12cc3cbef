"""ENAS architecture-search quickstart (BASELINE config[2]).

Parity: SURVEY.md §3.5 — runs the controller-driven cell search over
``JaxEnas``: search trials train briefly on shared supernet weights (one
compiled XLA graph for every proposed architecture), then the final
phase retrains the controller's best architecture from scratch.

    python examples/scripts/enas_search.py --synthetic --trials 10
"""

import argparse
import tempfile


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--synthetic", action="store_true")
    p.add_argument("--train")
    p.add_argument("--val")
    p.add_argument("--trials", type=int, default=10)
    args = p.parse_args()

    from rafiki_tpu.advisor import EnasAdvisor
    from rafiki_tpu.constants import BudgetOption, TrialStatus
    from rafiki_tpu.models import JaxEnas
    from rafiki_tpu.store import MetaStore, ParamStore
    from rafiki_tpu.worker import TrialRunner

    workdir = tempfile.mkdtemp(prefix="rafiki_enas_")
    if args.synthetic:
        from rafiki_tpu.datasets import make_synthetic_image_dataset
        args.train, args.val = make_synthetic_image_dataset(
            workdir, n_train=4096, n_val=512, image_shape=(32, 32, 3),
            n_classes=10, name="cifar10")
    if not args.train or not args.val:
        raise SystemExit("--train/--val or --synthetic is required")

    meta = MetaStore(":memory:")
    params = ParamStore(workdir + "/params")
    user = meta.create_user("enas@example.com", "h", "MODEL_DEVELOPER")
    model = meta.create_model(user["id"], "enas", "IMAGE_CLASSIFICATION",
                              "rafiki_tpu.models.enas:JaxEnas", {})
    budget = {BudgetOption.MODEL_TRIAL_COUNT: args.trials}
    job = meta.create_train_job(user["id"], "enas-app",
                                "IMAGE_CLASSIFICATION", budget,
                                args.train, args.val, "RUNNING")
    sub = meta.create_sub_train_job(job["id"], model["id"], "RUNNING")

    advisor = EnasAdvisor(JaxEnas.get_knob_config(), seed=0,
                          total_trials=args.trials)
    runner = TrialRunner(JaxEnas, advisor, args.train, args.val,
                         meta, params, sub["id"], model_id=model["id"],
                         budget=budget)
    runner.run()

    trials = sorted(meta.get_trials(sub["id"], TrialStatus.COMPLETED),
                    key=lambda t: t["no"])
    for t in trials:
        phase = ("final" if not t["knobs"].get("share_params") else "search")
        print(f"trial {t['no']:>3} [{phase}]  score={t['score']:.4f}")
    best = max(trials, key=lambda t: t["score"])
    print("best architecture:", best["knobs"]["arch"])
    print("ENAS_SEARCH OK")


if __name__ == "__main__":
    from rafiki_tpu.jaxenv import ensure_platform

    # Resolve the JAX platform up front: JAX_PLATFORMS=cpu pins the CPU;
    # anything else must find a TPU or the script refuses to run.
    ensure_platform()
    main()
