"""Model step: reads from the persistent compile cache before the
window opened, in seconds: jax's ``backend_compile_duration`` events
with a ``cache_hits`` event inside them on the same thread, the phase
``jit_cache_load`` (``phases.watch_jit``). Nested in the set-up phases,
not additive with them. 0 where the cache held nothing read; None
where nothing was traced either (the listener was off)
(setup_backend_s.py has the arithmetic)."""

from harness import load_module


def read(run):
    return load_module("metrics", "setup_backend_s").at_open(
        run, "jit_cache_load", seen="jit_trace")
