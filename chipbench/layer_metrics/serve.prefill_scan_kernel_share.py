"""Share of the window's whole-prompt prefills whose program computes every
recurrent layer's scan with the Pallas kernel (tpu_dist.ops.delta_scan)
rather than as ``jax.numpy`` (``SlotEngine.stats()["prefill_scan"]``:
``kernel_prefills`` / ``prefills``; each layer's ``takes_scan_kernel``
decides by the call: a decay a head takes it, a decay a channel does not).
A program without the counter, as the parent of PR 42 is, and a window
without a prefill report nothing."""


def read(run):
    scan = run.counters.get("engine", {}).get("prefill_scan")
    if not scan or not scan.get("prefills"):
        return None
    return 100.0 * scan.get("kernel_prefills", 0) / scan["prefills"]
