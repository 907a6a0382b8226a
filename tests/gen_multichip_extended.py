"""Regenerate MULTICHIP_EXTENDED.json — dryrun_multichip at {8, 16, 32} on
virtual CPU meshes.

Usage: ``python -m tests.gen_multichip_extended`` from the repo root.
``dryrun_multichip`` runs on the devices its process has, so each world
size gets its own child with that many CPU devices forced before JAX
starts; tests/test_dryrun_multichip.py runs the same children.
"""

import json
import os
import re
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dryrun_in_child(n_devices: int) -> None:
    """``__graft_entry__.dryrun_multichip(n)`` in a child holding an
    n-device CPU mesh; raises with the child's output on failure."""
    rest = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                  os.environ.get("XLA_FLAGS", ""))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{rest} --xla_force_host_platform_device_count="
                         f"{n_devices}".strip())
    proc = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__ as g; g.dryrun_multichip({n_devices})"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"dryrun_multichip({n_devices}) child failed "
            f"(rc={proc.returncode}):\n{proc.stdout[-2000:]}\n"
            f"{proc.stderr[-4000:]}")


def main():
    results = []
    for n in (8, 16, 32):
        t0 = time.time()
        try:
            dryrun_in_child(n)
            results.append({"n_devices": n, "ok": True,
                            "wall_s": round(time.time() - t0, 1)})
        except RuntimeError as e:  # record the failure rather than abort
            results.append({"n_devices": n, "ok": False,
                            "error": repr(e)[:500],
                            "wall_s": round(time.time() - t0, 1)})
    out = {
        "what": "dryrun_multichip on virtual CPU meshes: one train step "
                "per mesh config (dp, dp*sp ring/flash, dp*tp + TP "
                "decode, dp*pp, dp*ep, fsdp, dp*fsdp*tp) per world size",
        "reproduce": "python -m tests.gen_multichip_extended  (or pytest "
                     "tests/test_dryrun_multichip.py)",
        "results": results,
    }
    path = os.path.join(_REPO, "MULTICHIP_EXTENDED.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
