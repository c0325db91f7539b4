"""The processes that hold the chip: one per run, started by run.py."""
