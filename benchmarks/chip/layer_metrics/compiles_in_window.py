"""Programs the backend compiled inside the window: misses of the
persistent compilation cache between the window's start and its end,
counted by the ``jax.monitoring`` listener the child registers. The
limit is 0; above it the run is not correct. (Executables merely
fetched from the cache, as a ``jax.jit`` made anew per call does, are
on the run's earlier line as ``loads``.)"""


def read(evidence):
    return evidence["in_window"]["compiles"]
