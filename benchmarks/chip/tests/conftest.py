"""The benchmark's own checks, run by hand (``python -m pytest
benchmarks/chip/tests -q``): not tier-1. Everything here runs on the CPU,
the mesh tier on four of eight virtual devices, at a tiny scale: control
flow and answers, never a time."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
