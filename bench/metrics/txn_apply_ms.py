"""Mean ``txn.apply`` span (``MaterializedInstance.apply_txn``) per
transaction finished in the window, in milliseconds."""


def read(run):
    durs = [s["dur_ns"] for s in run.spans if s["name"] == "txn.apply"]
    return sum(durs) / len(durs) / 1e6 if durs else None
