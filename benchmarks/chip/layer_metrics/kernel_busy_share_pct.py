"""The serving kernel's share of device busy time in the traced slice:
device time of the Mosaic custom calls
(``harness.evidence.kernel_seconds``) over the union of all operation
intervals."""

from harness.evidence import kernel_seconds


def read(evidence):
    seconds = kernel_seconds(evidence)
    busy = (evidence.get("trace") or {}).get("busy_s")
    return 100.0 * seconds / busy if seconds and busy else None
