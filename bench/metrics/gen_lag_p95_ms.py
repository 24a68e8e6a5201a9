"""95th percentile (nearest rank) of how late each query of the window was
sent after it was due, in milliseconds: a starved generator shows here."""

from bench.stats import percentile


def read(run):
    lags = [q.sent - q.due for q in run.queries if q.sent is not None]
    return percentile(lags, 0.95) * 1e3 if lags else None
