"""XLA compilations, persistent-cache loads included, from the window's
first due request to its last answer, counted by a ``jax.monitoring``
listener.  Set-up warms every executable, so this should read 0."""


def read(run):
    return run.window.compiles
