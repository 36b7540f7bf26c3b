"""The benchmark: BENCHMARK.json's command, harness, yardstick and data."""
