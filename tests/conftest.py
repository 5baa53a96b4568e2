"""Test config: force CPU JAX with 8 virtual devices BEFORE jax imports.

Mirrors the reference's test strategy (SURVEY.md §4): sharding invariants
are tested single-process by enumerating part_index; multi-chip sharding
is tested on a virtual CPU mesh so CI needs no TPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

# JAX_PLATFORMS=cpu is applied through jax.config too
jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def tmpfile(tmp_path):
    def _make(name: str, content: bytes) -> str:
        p = tmp_path / name
        p.write_bytes(content)
        return str(p)
    return _make


@pytest.fixture
def rng():
    return np.random.RandomState(42)
