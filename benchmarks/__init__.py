"""Tier-1 smokes and CPU-side recordings that run beside the tests.

The chip's benchmark is ``chipbench/`` (BENCHMARK.json); nothing here times
the device.  ``bench_*.py`` each carry a ``--smoke`` gate a tier-1 test runs
for its bitwise and count assertions, and a full run that writes one
``BENCH_*.json`` on this CPU box; ``accuracy_run.py`` holds the convergence
gates, ``test_tiers.py`` times the pytest tiers.
"""
