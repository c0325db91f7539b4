"""Share of the window's transfers that took the dlpack rung, from
``ingest_stats()`` read in the child after the window."""


def read(evidence):
    ingest = evidence.get("ingest") or {}
    total = ingest.get("dlpack_transfers", 0) + ingest.get("host_transfers", 0)
    return 100.0 * ingest["dlpack_transfers"] / total if total else None
