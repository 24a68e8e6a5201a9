"""Seconds to materialize the fixpoint: ``MaterializedInstance(...)`` on the
host clock, ended by waiting on the published epoch's device arrays."""


def read(run):
    return run.materialize_s
