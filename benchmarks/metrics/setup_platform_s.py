"""Admin + scheduler: the platform's start, in seconds: the set-up
phase ``platform_start``, the whole of ``LocalPlatform.__init__``
(stores, bus, services manager, admin, HTTP app, supervisor threads),
at the window's opening (setup_backend_s.py has the arithmetic)."""

from harness import load_module


def read(run):
    return load_module("metrics", "setup_backend_s").at_open(
        run, "platform_start")
