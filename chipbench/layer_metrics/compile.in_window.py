"""Programs compiled, or fetched from the persistent cache, between the
window's first and last instant (jax.monitoring backend-compile events).
Must be 0: every shape the window uses was warmed up in set-up."""


def read(run):
    return run.counters["compiles_in_window"]
