"""The benchmark's own tests run on the CPU, like the repo's: every one is
a check of the yardstick (generators, operation counts, the reference, the
trace reduction, the control), never a measurement."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
