"""Test harness: run everything on an 8-device virtual CPU mesh.

Mirrors the reference's CI strategy of simulating multi-device on one box
(SURVEY.md §4.5: tools/launch.py local launcher → here
xla_force_host_platform_device_count). The chip is exercised by
chip_smoke.py, not the unit suite.
"""
import os

# MXTPU_REAL_TPU=1 keeps the real accelerator visible (used by
# tests/tpu/test_parity.py on the chip); default CI forces the
# virtual CPU mesh.
_REAL = os.environ.get("MXTPU_REAL_TPU") == "1"
if not _REAL:
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("MXNET_SEED", "17")

import jax

if not _REAL:
    jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(170)
