"""Programs lowered (compiled, or loaded from the compile cache) inside
the window, from a ``jax.monitoring`` listener.  Set-up warms every shape
the window uses, so this should read 0."""
UNIT = "count"


def read(run):
    return run.record["compiles_in_window"]
