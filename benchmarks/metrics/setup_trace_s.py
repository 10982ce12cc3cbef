"""Model step: what tracing and lowering cost before the window opened,
in seconds: jax's own ``jaxpr_trace_duration`` and
``jaxpr_to_mlir_module_duration`` events as the phases ``jit_trace`` and
``jit_lower`` (``phases.watch_jit``: each stage's union of intervals on
a thread, so a jit inside another counts once). Paid in every process,
whatever the compile cache holds. Nested in the set-up phases, not
additive with them; what the second trial traced before the window
opened is in it too (setup_backend_s.py has the arithmetic)."""

from harness import load_module


def read(run):
    return load_module("metrics", "setup_backend_s").at_open(
        run, "jit_trace", "jit_lower")
