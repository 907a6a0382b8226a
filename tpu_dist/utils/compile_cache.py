"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so a path that moves never hits: the
location is either the one the environment names or one fixed path inside
the checkout — never one built from ``tempfile``, a pid or the time.
"""

from __future__ import annotations

import os

__all__ = ["ensure_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one place and return it.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, nothing is
    touched.  Unset: ``<checkout>/.jax_cache`` (gitignored).  Idempotent;
    call before the first compilation — ``init_process_group``,
    ``SlotEngine``, ``examples/serve_lm.py`` and ``chip_smoke.py`` do.
    Launcher children inherit the environment and so share the directory.

    Either way the compile ledger (:mod:`tpu_dist.obs.compiles`) starts
    listening here, once a process: what compiles after this call is on it.
    """
    from ..obs.compiles import install
    install()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
