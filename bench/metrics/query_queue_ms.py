"""Mean queue wait of the window's queries, from submission to admission into
a query batch (``queue_wait_s`` on each ``query`` span; ``DatalogServer``
observes the same time into ``datalog_queue_wait_seconds{kind="query"}``),
in milliseconds; None where the spans carry no queue wait."""


def read(run):
    waits = [s["args"]["queue_wait_s"] for s in run.spans
             if s["name"] == "query" and "queue_wait_s" in s["args"]]
    return sum(waits) / len(waits) * 1e3 if waits else None
