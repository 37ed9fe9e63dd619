"""The benchmark's own tests: CPU only, tiny sizes, four virtual devices (a
runner across chips can be rehearsed here). Run them with

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They are not part of the repository's tier-1 suite (``tests/``)."""

import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
