"""(busiest chip - least busy chip) / busiest chip, by device busy time
in the traced slice."""


def read(evidence):
    busy = [d["busy_s"] for d in (evidence.get("trace") or {}).get("devices") or []]
    if len(busy) < 2 or not max(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
