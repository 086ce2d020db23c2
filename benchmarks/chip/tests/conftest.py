"""The benchmark's tests run on the CPU with four virtual devices, so that
the four-chip driver runs too. These settings must precede JAX's start."""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

HERE = pathlib.Path(__file__).resolve().parents[1]
for p in (HERE, HERE.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
