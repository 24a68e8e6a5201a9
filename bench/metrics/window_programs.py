"""XLA executables built or loaded from the persistent cache inside the
measured window (``jax.monitoring``); 0 when set-up warmed every shape."""


def read(run):
    return run.window_build["compiles"]
