"""The benchmark BENCHMARK.json names: harness, configurations, traffic
mixes, metric readers and the yardsticks they share. See run.py."""
