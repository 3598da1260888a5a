"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

Four host devices stand in for the four chips of a 2x2 host, and the
``bench`` package and the system under test (``src``) are importable.
"""

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags +
                               " --xla_force_host_platform_device_count=4")
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
