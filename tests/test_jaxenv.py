"""Platform resolution (rafiki_tpu.jaxenv): explicit CPU or a real TPU.

``ensure_platform`` has two outcomes: ``JAX_PLATFORMS=cpu`` pins the CPU
backend (and sizes its virtual device pool); anything else must find a
TPU in this process or raise — no probe, no fallback. It also places the
persistent compilation cache: wherever ``JAX_COMPILATION_CACHE_DIR``
says, else a fixed directory inside the checkout (chip runs only). No
test here opens an accelerator backend.
"""

import os
import subprocess
import sys

import pytest

from rafiki_tpu import jaxenv

TIMEOUT = 120
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code: str, **env_overrides) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=TIMEOUT,
                          cwd=REPO)


@pytest.fixture()
def restore_cache_dir():
    """In-process calls repoint this worker's persistent cache; put it
    back so later tests of the same worker compile as before."""
    import jax

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_initialized_backend_wins():
    import jax

    jax.devices()  # force backend init (conftest pinned cpu config)
    assert jaxenv.backend_initialized()
    assert jaxenv.ensure_platform() == "cpu"
    assert jaxenv.ensure_platform(n_virtual_devices=4) == "cpu"
    assert len(jax.devices()) == 8  # a live backend is never resized


def test_explicit_cpu_request_is_honoured():
    """JAX_PLATFORMS=cpu yields the CPU backend and never looks for an
    accelerator."""
    r = _child(
        "from rafiki_tpu.jaxenv import ensure_platform\n"
        "import jax\n"
        "p = ensure_platform()\n"
        "assert p == 'cpu', p\n"
        "assert jax.devices()[0].platform == 'cpu'\n"
        "print('OK')\n",
        JAX_PLATFORMS="cpu")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_virtual_device_pool_sizing():
    r = _child(
        "from rafiki_tpu.jaxenv import ensure_platform\n"
        "import jax\n"
        "ensure_platform(n_virtual_devices=4)\n"
        "assert len(jax.devices()) == 4, jax.devices()\n"
        "print('OK')\n",
        JAX_PLATFORMS="cpu", XLA_FLAGS="")
    assert r.returncode == 0, r.stdout + r.stderr


def test_force_cpu_device_count_after_init():
    """entry()-then-dryrun in one process: a 1-device backend already
    initialized must be replaceable by an 8-device virtual CPU pool."""
    r = _child(
        "import jax\n"
        "from rafiki_tpu import jaxenv\n"
        "jaxenv.ensure_platform()\n"
        "assert len(jax.devices()) == 1, jax.devices()\n"
        "jaxenv.force_cpu_device_count(8)\n"
        "assert len(jax.devices()) == 8, jax.devices()\n"
        "import numpy as np\n"
        "x = jax.jit(lambda a: a * 2)(np.arange(4.0))\n"
        "assert float(x.sum()) == 12.0\n"
        "print('OK')\n",
        JAX_PLATFORMS="cpu", XLA_FLAGS="")
    assert r.returncode == 0, r.stdout + r.stderr


@pytest.mark.parametrize("request_", ["", "tpu,cpu", "cpu,tpu"])
def test_no_cpu_request_and_no_tpu_raises(monkeypatch, restore_cache_dir,
                                          request_):
    """Anything but an exact ``cpu`` request needs a TPU: with none,
    ensure_platform raises instead of continuing on the CPU. The device
    list is patched — no backend is opened."""
    import jax

    monkeypatch.setenv("JAX_PLATFORMS", request_)
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice("cpu")])
    with pytest.raises(RuntimeError, match="no TPU"):
        jaxenv.ensure_platform()
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeDevice("tpu")])
    assert jaxenv.ensure_platform() == "tpu"


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


def test_virtual_devices_refused_without_cpu_request(monkeypatch):
    """Resizing clears the live backend — never done to one that may
    hold the chip (dryrun_multichip on a TPU with too few chips)."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        jaxenv.force_cpu_device_count(8)


# A chip run in a child process, without a chip: the device list is
# patched before ensure_platform looks at it, so no backend is opened.
_TPU_RUN = (
    "import jax\n"
    "jax.devices = lambda *a: [type('D', (), {'platform': 'tpu'})()]\n"
    "from rafiki_tpu import jaxenv\n"
    "assert jaxenv.ensure_platform() == 'tpu'\n"
    "first = jax.config.jax_compilation_cache_dir\n"
    "assert jaxenv.ensure_platform() == 'tpu'\n"
    "assert jax.config.jax_compilation_cache_dir == first\n"
    "print('DIR', first)\n")


def test_cache_dir_untouched_when_operator_placed_it(tmp_path):
    r = _child(_TPU_RUN, JAX_PLATFORMS="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.strip() == f"DIR {tmp_path}"


def test_cache_dir_is_fixed_in_checkout_path():
    """Unset, a chip run's cache lives at <checkout>/.jax_cache — the
    same path in every call and every process (the path is part of the
    cache key), derived from the package's location only."""
    runs = [_child(_TPU_RUN, JAX_PLATFORMS="") for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stdout + r.stderr
    want = os.path.join(REPO, ".jax_cache")
    assert jaxenv.COMPILE_CACHE_DIR == want
    assert [r.stdout.strip() for r in runs] == [f"DIR {want}"] * 2


def test_explicit_cpu_run_gets_no_cache_from_code():
    r = _child(
        "from rafiki_tpu import jaxenv\n"
        "import jax\n"
        "jaxenv.ensure_platform()\n"
        "print('DIR', jax.config.jax_compilation_cache_dir)\n",
        JAX_PLATFORMS="cpu")
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.strip() == "DIR None"


def _smoke(*argv, **env_overrides):
    env = dict(os.environ)
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        env=env, capture_output=True, text=True, timeout=TIMEOUT,
        cwd=REPO)


def test_chip_smoke_help_parses():
    r = _smoke("--help")
    assert r.returncode == 0, r.stderr
    assert "--chips" in r.stdout


def test_chip_smoke_refuses_the_cpu():
    """The smoke proves a chip run: on the CPU it exits non-zero and
    prints no result line."""
    r = _smoke(JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
