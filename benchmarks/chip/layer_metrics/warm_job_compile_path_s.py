"""The compile path's seconds in the warm-up job, which is set-up:
``trace_s + lower_s + backend_s + cache_load_s`` of the warm-up job's
``build_status.json["compile"]``. With a warm persistent cache it is
tracing, lowering and loading every program of the cell once; with a
cold one ``backend_s`` is the compilations. None where the program
writes no ``compile``."""

SECONDS = ("trace_s", "lower_s", "backend_s", "cache_load_s")


def read(evidence):
    found = ((evidence.get("warm_job") or {}).get("status") or {}).get("compile")
    if not found:
        return None
    return sum(found[key] for key in SECONDS)
