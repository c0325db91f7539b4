"""One reader per per-layer metric, found by the metric's name. A reader
takes the run's evidence (README.md) and returns a number, or None where
its source gave nothing to read."""
