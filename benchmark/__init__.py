"""The benchmark's own code and data: the yardstick later PRs are measured
with and may add files to but not edit. Entry point: benchmark/run.py."""
