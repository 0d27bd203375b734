"""One reader per per-layer metric: `read(run)` returns the number, or
None where there is nothing to read (the metric is then left out)."""
