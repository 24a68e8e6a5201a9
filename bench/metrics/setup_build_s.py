"""Seconds of program building during set-up: backend compiles and loads from
the persistent cache, plus tracing and lowering (``jax.monitoring`` events)."""


def read(run):
    b = run.setup_build
    return b["compile_seconds"] + b["trace_seconds"]
