"""The benchmark: cells, traffic, drivers, per-layer readers and the yardstick (see PERF.md)."""
