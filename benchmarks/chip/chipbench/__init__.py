"""Chip benchmark harness: one cell of ``BENCHMARK.json`` run once.

Configurations, traffic mixes and metrics are data and small readers under
``configs/``, ``traffic/``, ``metrics/`` and ``references/``, found by the
names in ``BENCHMARK.json``; this package is the general code they share."""
