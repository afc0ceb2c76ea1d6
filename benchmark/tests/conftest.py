"""The benchmark's own tests run on the CPU in seconds:

    python -m pytest benchmark/tests -q

Four virtual CPU devices stand in for the 2x2 mesh.  Nothing here
measures a time; the chip is needed for that.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
