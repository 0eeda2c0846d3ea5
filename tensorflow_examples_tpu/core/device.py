"""Which device this process runs on, and where it keeps compiled code.

Three decisions every entry point (train/eval/serve/generate CLIs,
bench.py, the diag tools, tests_tpu/) used to make for itself, each
with its own quiet fallback, live here once:

* ``require_device`` — ``--device=tpu`` means the TPU. A process asked
  for one platform that finds another stops at start-up; it never runs
  on (and reports numbers from) whatever JAX happened to pick.
* ``pallas_interpret`` — the Pallas kernels under ``ops/`` run compiled
  by Mosaic on ``tpu`` and in interpret mode on ``cpu`` (the tests).
  Any other platform is an error, not a quiet interpret.
* ``enable_compile_cache`` — JAX's persistent compilation cache at one
  fixed place, or wherever ``JAX_COMPILATION_CACHE_DIR`` says.
"""

from __future__ import annotations

import functools
import json
import logging
import os

import jax

log = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
# Fixed, inside the checkout, never a pid/temp/timestamp name: a cache
# that moves between runs never hits.
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its
    directory. With ``JAX_COMPILATION_CACHE_DIR`` set JAX has already
    read it — nothing is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``. The only place in the repo that assigns
    ``jax_compilation_cache_dir``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def describe_devices() -> str:
    """The one start-up line that names the device (chip_smoke.py
    parses it): platform, device kind, local/global device count and
    this process's index, as JAX reports them."""
    dev = jax.devices()[0]
    return (
        f"device: platform={dev.platform} "
        f"device_kind={json.dumps(dev.device_kind)} "
        f"local_devices={jax.local_device_count()} "
        f"global_devices={jax.device_count()} "
        f"process_index={jax.process_index()}"
    )


def require_device(device: str) -> None:
    """Stop unless JAX's default backend is ``device`` (what
    ``--device`` named; ``"tpu"`` for bench.py and the diag tools).
    Call after ``distributed.initialize()`` — this initializes the
    backend."""
    found = jax.default_backend()
    if found != device:
        raise SystemExit(
            f"this run is for the {device!r} platform (--device={device}) "
            f"but JAX's default backend is {found!r} "
            f"({jax.devices()[0].device_kind}); nothing ran. Tests and "
            "tiny-size runs use --device=cpu / JAX_PLATFORMS=cpu."
        )
    log.info(describe_devices())


@functools.lru_cache(maxsize=None)
def pallas_interpret(kernel: str) -> bool:
    """Whether Pallas kernel ``kernel`` runs in interpret mode here:
    ``cpu`` -> True (the tests), ``tpu`` -> False (Mosaic-compiled).
    Logged once per kernel per process, so a run's log says which
    kernels it traced and how they ran."""
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernel {kernel!r}: no rule for platform "
            f"{platform!r} — these kernels are compiled for 'tpu' and "
            "interpreted on 'cpu' (tests); pass interpret= explicitly "
            "to run anywhere else"
        )
    interpret = platform == "cpu"
    log.info(
        "pallas kernel %s: %s on %s", kernel,
        "interpret mode" if interpret else "compiled by Mosaic", platform,
    )
    return interpret
