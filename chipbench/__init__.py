"""chipbench — the on-chip benchmark of tpu_dist (BENCHMARK.json's ``paths``).

The yardstick lives here, where a PR that claims a gain cannot change it:
traffic generation, the load generator, the reduction from traces and spans
to metrics, the peaks table, the FLOP and byte counts, the plain references
and the comparison that decides ``correct``.  From the program it takes the
system under test, its counters and its kernel names.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name BENCHMARK.json
gives it; PERF.md says how to add one.
"""
