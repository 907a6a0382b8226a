"""chipbench's own tests: `python -m pytest chipbench/tests -q`, on the CPU.

Not part of tier-1 (`pytest.ini` collects `tests/` only).  A CPU run checks
results, control flow and counts; it never yields a device number.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"   # before the first jax import

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
