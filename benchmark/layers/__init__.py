"""Per-layer metric readers: benchmark/layers/<name up to the first '.'>.py.

Each has read(metric_name, run) -> a number, or None where the run holds
nothing to read (the harness then leaves the metric out of the line).
"""
