"""Programs of set-up that the persistent cache was asked for and did not
hold (the compile ledger's ``cache == "miss"``, tpu_dist.obs.compiles): each
was compiled anew.  The cache keeps only what took
``jax_persistent_cache_min_compile_time_secs`` to compile, so a small program
misses at every start; the ledger's ``kept`` tells those apart."""

from chipbench import compiles


def read(run):
    ledger = compiles.setup(run)
    return ledger["misses"] if ledger else None
