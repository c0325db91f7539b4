"""``device_idle_pct`` in a serve cell, where it moves another end-to-end metric
than in a build cell (a per-layer metric names one); the same reader."""

from harness.manifest import load_module, ROOT

read = load_module(ROOT, "layer_metrics", "device_idle_pct").read
