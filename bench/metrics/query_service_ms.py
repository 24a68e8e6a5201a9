"""``serve.queries`` span time (``DatalogServer`` query batches) per query
served in the window, in milliseconds."""


def read(run):
    spans = [s for s in run.spans if s["name"] == "serve.queries"]
    served = sum(s["args"].get("batch", 0) for s in spans)
    return sum(s["dur_ns"] for s in spans) / served / 1e6 if served else None
