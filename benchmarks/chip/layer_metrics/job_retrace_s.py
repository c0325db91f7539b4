"""Seconds a window job spends on the way to executables it already
has: ``trace_s + lower_s + backend_s + cache_load_s`` of
``build_status.json["compile"]`` (the program's ``jax.monitoring``
duration listener: tracing to a jaxpr, lowering to MLIR, the backend's
compile call net of cache reads, reading the persistent cache), over one
build; median over the window's jobs. Nothing compiles in the window
(``compiles_in_window``); this is what a ``jax.jit`` made anew costs.
None where the program writes no ``compile``."""

from harness.stats import median

SECONDS = ("trace_s", "lower_s", "backend_s", "cache_load_s")


def read(evidence):
    seconds = []
    for job in evidence["jobs"]:
        found = (job.get("status") or {}).get("compile")
        if not found:
            return None
        seconds.append(sum(found[key] for key in SECONDS))
    return median(seconds)
