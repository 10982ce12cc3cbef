"""Client / HTTP edge: what of ``setup_s`` no program span covers, in
seconds: ``setup_s`` less the TPU runtime's start, the platform's, the
job's way to its first trial and the warm-up trial (the readers
setup_backend_s, setup_platform_s, setup_submit_s, setup_warmup_s).
That is the interpreter's imports, the benchmark's own data, the
client's logins over HTTP, the services' launch. The parts it subtracts
do not overlap in time, so it is never below 0 but by the few
milliseconds of the warm-up's tail after its row is completed."""

from harness import load_module

PARTS = ("setup_backend_s", "setup_platform_s", "setup_submit_s",
         "setup_warmup_s")


def read(run):
    parts = [load_module("metrics", name).read(run) for name in PARTS]
    if None in parts:
        return None
    return run["setup_s"] - sum(parts)
