"""JAX platform resolution: an explicit CPU request or a real TPU.

Parity: SURVEY.md §1 L0 ("TPU rebuild mapping") — the reference assumes
CUDA is either present or absent at process start. Here the accelerator
is a directly attached TPU, and a run that silently lands on the host
CPU is worse than one that fails: it trains, serves and "benchmarks"
with exit code 0 on the wrong device. So there are exactly two
outcomes:

- ``JAX_PLATFORMS`` is exactly ``cpu`` (tests, virtual-device
  rehearsals): pin the CPU backend and size its virtual device pool.
- anything else: initialise the backend in THIS process and raise
  unless the first device is a TPU. A chip belongs to one process at a
  time and raises on failure (it does not hang), so there is no probe
  child and no fallback.

``ensure_platform()`` is the first call of every entry point (serve
CLI, chip_smoke.py, ``benchmarks/``, example scripts,
``__graft_entry__``). It
is also the one place that places JAX's persistent compilation cache
for a chip run: wherever ``JAX_COMPILATION_CACHE_DIR`` says, else a
fixed directory inside the checkout. An explicit CPU run gets no cache
from code (XLA's CPU loader logs machine-feature errors on every entry
it reads back, and nothing there takes long to compile).
"""

from __future__ import annotations

import os
import re
from typing import Optional

#: Where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR``
#: does not say otherwise: a fixed path inside the checkout (the path
#: is part of the cache key, so a directory that moves never hits).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def cpu_requested() -> bool:
    """True when the operator asked for the CPU, and only the CPU."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def backend_initialized() -> bool:
    """True once any XLA backend exists (platform can no longer change)."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def _place_compile_cache() -> None:
    """Point the persistent compilation cache at its directory (before
    the first compile: jax latches the cache on first use).

    An operator who sets ``JAX_COMPILATION_CACHE_DIR`` has already
    placed it (JAX reads the variable itself); code sets nothing then.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def _xla_flags_with_device_count(n: int, *, override: bool) -> str:
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" not in flags:
        return (flags + " " + flag).strip()
    if not override:
        return flags
    return re.sub(r"--xla_force_host_platform_device_count=\d+", flag,
                  flags)


def _ensure_virtual_devices(n: int) -> None:
    """Make the CPU backend expose >= n devices (must precede init)."""
    os.environ["XLA_FLAGS"] = _xla_flags_with_device_count(
        n, override=False)


def _pin_cpu(n_virtual_devices: Optional[int]) -> str:
    import jax

    if n_virtual_devices:
        _ensure_virtual_devices(n_virtual_devices)
    jax.config.update("jax_platforms", "cpu")
    return "cpu"


def force_cpu_device_count(n: int) -> None:
    """Re-initialize onto a CPU backend with exactly ``n`` devices.

    Only under an explicit cpu request. Unlike :func:`ensure_platform`,
    this works even after a backend was initialized (e.g. ``entry()``
    ran on a 1-device backend and the driver then wants an 8-device dry
    run in the same process): it clears the live CPU backend so the
    next ``jax.devices()`` re-reads the device count. Arrays created on
    the old backend remain readable but must not be mixed into new
    computations. A live TPU backend is never cleared — it holds the
    chip.
    """
    import jax

    if not cpu_requested():
        raise RuntimeError(
            f"{n} virtual CPU devices need JAX_PLATFORMS=cpu; a backend "
            f"that may hold the chip is never cleared")
    # XLA_FLAGS is parsed once per process, so mutating it cannot resize
    # a live backend — but keep it in sync for spawned children.
    os.environ["XLA_FLAGS"] = _xla_flags_with_device_count(
        n, override=True)
    if backend_initialized():
        from jax.extend.backend import clear_backends

        clear_backends()
    # jax_num_cpu_devices IS re-read on the next backend construction.
    jax.config.update("jax_num_cpu_devices", n)
    jax.config.update("jax_platforms", "cpu")


#: Whether ``ensure_platform`` has timed its first resolve.
_backend_timed = False


def ensure_platform(*, n_virtual_devices: Optional[int] = None) -> str:
    """Resolve the JAX platform; returns ``"cpu"`` or ``"tpu"``.

    With ``JAX_PLATFORMS=cpu`` the CPU backend is pinned
    (``n_virtual_devices`` sizes its virtual pool for sharding tests and
    multi-chip dry runs; no-op if ``XLA_FLAGS`` already pins a count or
    the backend is live). Otherwise the backend is initialised here and
    must be a TPU: no TPU raises ``RuntimeError`` — nothing continues
    on the CPU by accident.

    Idempotent; safe to call from every entry point. The first call
    that finds no backend up is the phase ``backend_init`` (the TPU
    runtime's start); every call installs ``phases.watch_jit`` once.
    """
    global _backend_timed
    from .observe import phases

    phases.watch_jit()
    if _backend_timed or backend_initialized():
        return _resolve(n_virtual_devices)
    _backend_timed = True
    with phases.span("backend_init"):
        return _resolve(n_virtual_devices)


def _resolve(n_virtual_devices: Optional[int]) -> str:
    import jax

    if cpu_requested():
        if not backend_initialized():
            return _pin_cpu(n_virtual_devices)
        platform = jax.devices()[0].platform
        if platform != "cpu":
            raise RuntimeError(
                f"JAX_PLATFORMS=cpu was requested but the backend is "
                f"already initialized on {platform!r}")
        return "cpu"
    _place_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax resolved platform {platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). "
            f"Set JAX_PLATFORMS=cpu to run on the CPU on purpose.")
    return "tpu"
