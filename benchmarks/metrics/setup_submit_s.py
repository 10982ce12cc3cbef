"""Admin + scheduler: a job's way from upload to its first trial, in
seconds: the set-up phases ``model_upload`` (``Admin.create_model``),
``job_submit`` (``Admin.create_train_job`` up to the services' launch)
and ``worker_start`` (``TrainWorker.run`` up to the runner's loop),
summed at the window's opening (setup_backend_s.py has the
arithmetic)."""

from harness import load_module

PHASES = ("model_upload", "job_submit", "worker_start")


def read(run):
    return load_module("metrics", "setup_backend_s").at_open(run, *PHASES)
