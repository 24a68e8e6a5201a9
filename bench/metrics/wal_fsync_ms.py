"""Mean ``wal.fsync`` span (``persist/wal.py``) per commit group in the
window, in milliseconds."""


def read(run):
    durs = [s["dur_ns"] for s in run.spans if s["name"] == "wal.fsync"]
    return sum(durs) / len(durs) / 1e6 if durs else None
