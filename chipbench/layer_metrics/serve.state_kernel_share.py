"""Share of the window's decode steps whose program computes every recurrent
layer's one-token update with the Pallas kernel (tpu_dist.ops.delta_step)
rather than as ``jax.numpy`` (``SlotEngine.stats()["state"]``:
``kernel_steps`` / ``steps``; each layer's ``takes_step_kernel`` decides by
the call).  A program without the counter, as the parent of PR 41 is, and a
window without a decode step report nothing."""


def read(run):
    state = run.counters.get("engine", {}).get("state")
    if not state or not state.get("steps"):
        return None
    return 100.0 * state.get("kernel_steps", 0) / state["steps"]
