"""The data provider's share of a machine's fetch: ``provider_read`` /
(``provider_read`` + ``resample_join`` + ``row_filter``), thread-seconds
of the ``data_fetch`` phase's parts in ``build_status.json``; median
over the window's jobs. The cells read a synthetic ``RandomDataProvider``,
so this share is what the benchmark's own data costs; the rest is the
``TimeSeriesDataset`` work every provider's user pays. None where the
program records no parts."""

from harness.stats import median

PARTS = ("provider_read", "resample_join", "row_filter")


def read(evidence):
    shares = []
    for job in evidence["jobs"]:
        phase = ((job.get("status") or {}).get("phases") or {}).get("data_fetch") or {}
        parts = phase.get("parts") or {}
        total = sum(parts[p]["seconds"] for p in PARTS if p in parts)
        if "provider_read" not in parts or not total:
            return None
        shares.append(100.0 * parts["provider_read"]["seconds"] / total)
    return median(shares)
