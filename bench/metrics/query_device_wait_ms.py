"""Time the host waited on the device for the window's lookups
(``device.sync`` spans with ``what="query_rows"``: every host read of one
lookup in ``MaterializedInstance._query_in``) per query answered in the
window (``query`` spans), in milliseconds."""


def read(run):
    queries = sum(1 for s in run.spans if s["name"] == "query")
    waits = [s["dur_ns"] for s in run.spans if s["name"] == "device.sync"
             and s["args"].get("what") == "query_rows"]
    return sum(waits) / queries / 1e6 if queries else None
