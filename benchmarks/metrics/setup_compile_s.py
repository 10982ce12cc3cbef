"""Model step: XLA compiles before the window opened, in seconds: jax's
``backend_compile_duration`` events with no persistent-cache hit inside
them, the phase ``jit_compile`` (``phases.watch_jit``). Near 0 where
the cache holds every program worth caching; the whole compile on a
checkout's first run. Nested in the set-up phases, not additive with
them. 0 where nothing compiled; None where nothing was traced either
(the listener was off) (setup_backend_s.py has the arithmetic)."""

from harness import load_module


def read(run):
    return load_module("metrics", "setup_backend_s").at_open(
        run, "jit_compile", seen="jit_trace")
