"""The chip benchmark's harness: manifest, process control, statistics,
the comparison that decides ``correct`` and the last line."""
