"""Per-transaction time of strata updated on the PBME bit matrices
(``stratum`` spans whose ``mode`` is ``bitmatrix``; ``core/bitmatrix.py``
plus the merge into the tuple table), in ms; None when no stratum ran on
the bit matrices."""


def read(run):
    txns = sum(1 for s in run.spans if s["name"] == "txn.apply")
    durs = [s["dur_ns"] for s in run.spans
            if s["name"] == "stratum" and s["args"].get("mode") == "bitmatrix"]
    return sum(durs) / txns / 1e6 if durs and txns else None
