"""On-chip benchmark: a data-driven harness over BENCHMARK.json's cells."""
