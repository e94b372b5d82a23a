"""Readers of per-layer metrics that need code: `read(run, spec)` gives
the value, or None where there is nothing to read (the harness then
leaves the metric out of the line)."""
