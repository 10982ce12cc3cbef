"""Model-developer quickstart: upload a custom model file and train it.

Parity: SURVEY.md §2 "Quickstart scripts" + §3.4 — the upstream
model-developer flow: write a BaseModel subclass in a file, upload it
(the platform stores the source and re-materialises the class inside
workers), then run a train job against it.

    python examples/scripts/model_developer.py --local --synthetic
"""

import argparse
import os
import tempfile

MODEL_FILE = os.path.join(os.path.dirname(__file__), "..", "models",
                          "my_model.py")


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--admin-host", default="127.0.0.1")
    p.add_argument("--admin-port", type=int, default=3000)
    p.add_argument("--local", action="store_true")
    p.add_argument("--synthetic", action="store_true")
    p.add_argument("--train")
    p.add_argument("--val")
    p.add_argument("--model-file", default=MODEL_FILE)
    args = p.parse_args()

    from rafiki_tpu.client import Client
    from rafiki_tpu.constants import BudgetOption, TaskType, UserType

    workdir = tempfile.mkdtemp(prefix="rafiki_mdev_")
    platform = None
    if args.local:
        from rafiki_tpu.platform import LocalPlatform
        platform = LocalPlatform(workdir=workdir, http=True)
        args.admin_port = platform.admin_port
    if args.synthetic:
        from rafiki_tpu.datasets import make_synthetic_image_dataset
        args.train, args.val = make_synthetic_image_dataset(
            workdir, n_train=1024, n_val=128)
    if not args.train or not args.val:
        raise SystemExit("--train/--val or --synthetic is required")

    try:
        root = Client(args.admin_host, args.admin_port)
        root.login("superadmin@rafiki", "rafiki")
        try:
            root.create_user("mdev@example.com", "pw",
                             UserType.MODEL_DEVELOPER)
        except Exception:
            pass

        dev = Client(args.admin_host, args.admin_port)
        dev.login("mdev@example.com", "pw")

        # Upload the model FILE: the class is re-created from this source
        # inside each worker, exactly like upstream's model upload.
        model = dev.create_model("my-model", TaskType.IMAGE_CLASSIFICATION,
                                 "MyModel", model_file_path=args.model_file)
        print("uploaded model:", model["id"])

        job = dev.create_train_job(
            "mdev-app", TaskType.IMAGE_CLASSIFICATION, [model["id"]],
            {BudgetOption.MODEL_TRIAL_COUNT: 2}, args.train, args.val)
        done = dev.wait_until_train_job_done(job["id"], timeout=3600)
        assert done["status"] == "STOPPED", done
        best = dev.get_best_trials_of_train_job(job["id"], max_count=1)
        print("best trial score:", round(best[0]["score"], 4))
        print("MODEL_DEVELOPER OK")
    finally:
        if platform is not None:
            platform.shutdown()


if __name__ == "__main__":
    from rafiki_tpu.jaxenv import ensure_platform

    # Resolve the JAX platform up front: JAX_PLATFORMS=cpu pins the CPU;
    # anything else must find a TPU or the script refuses to run.
    ensure_platform()
    main()
