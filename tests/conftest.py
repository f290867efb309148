"""Test config: run everything on a virtual 8-device CPU mesh so multi-chip
sharding paths compile and execute without TPU hardware (chip_smoke.py is
the run on the real chip).

JAX_PLATFORMS is an ordinary environment variable: the tier-1 command sets
it to `cpu`, and this file pins it for bare `pytest` runs too.  The eight
virtual devices come from XLA_FLAGS, which XLA reads at the first backend
use.  x64 is ON here and OFF everywhere else (JAX's default, what the chip
runs): a dtype-sensitive path is only proven where x64 is off — by
chip_smoke.py on the chip, or by a tools/*_smoke.py run as a script.
"""
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: medium-shape dryruns (seq-512 numerics checks)")
