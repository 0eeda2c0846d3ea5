"""Compiled-kernel tests — they run on the TPU or they fail.

``tests/conftest.py`` pins the CPU backend so that suite runs anywhere;
this directory is the opposite: it proves the Pallas kernels compiled by
Mosaic on the real chip, so a run that finds no chip is a FAILURE — not
zero tests collected, not thirteen skips.

Run it as its own process (a chip belongs to one process at a time):

    python -m pytest tests_tpu/ -q

In this repo's sandbox the chip is reached through the chip tool named
in the builder's instructions; ``python chip_smoke.py`` goes first.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_sessionstart(session):
    import jax

    from tensorflow_examples_tpu.core.device import (
        describe_devices,
        enable_compile_cache,
    )

    enable_compile_cache()
    if jax.default_backend() != "tpu":
        pytest.exit(
            "tests_tpu/ needs the TPU backend; JAX found "
            f"{jax.default_backend()!r} ({describe_devices()}). The CPU "
            "suite is tests/.",
            returncode=1,
        )
    print(describe_devices())
