"""Chip benchmark of the dedup checkpoint store: one cell per entry of
``BENCHMARK.json``'s ``workloads``, run by ``python3 -m chipbench.run``."""
