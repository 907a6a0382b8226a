"""Pin the 16/32-device dryrun claims as reproducible-from-repo.

``__graft_entry__.dryrun_multichip(n)`` runs on the devices its process
has and raises with fewer, so each size gets a child holding an n-device
virtual CPU mesh forced before JAX starts (tests/gen_multichip_extended.py,
which also regenerates ``MULTICHIP_EXTENDED.json`` from the same runs).

Each size compiles and executes one train step per mesh config (dp,
dp x sp ring/flash, dp x tp + TP decode, dp x pp, dp x ep, fsdp, and the
3-D dp x fsdp x tp) — several minutes of CPU compile work, hence slow
tier.  The 8-device configs run in-process in tests/test_chip_smoke.py.
"""

import pytest

from gen_multichip_extended import dryrun_in_child

pytestmark = pytest.mark.slow


@pytest.mark.parametrize("n_devices", [16, 32])
def test_dryrun_multichip_large_worlds(n_devices):
    dryrun_in_child(n_devices)
