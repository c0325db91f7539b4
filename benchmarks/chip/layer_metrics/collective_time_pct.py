"""Device time of collective operations (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute: ``xplane.category``) in
the traced slice over device busy time, over every chip."""


def read(evidence):
    devices = (evidence.get("trace") or {}).get("devices") or []
    busy = sum(d["busy_s"] for d in devices)
    if not busy:
        return None
    return 100.0 * sum(d["collective_seconds"] for d in devices) / busy
