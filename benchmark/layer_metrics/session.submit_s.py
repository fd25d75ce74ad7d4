"""Layer metric ``session.submit_s``: the median of the benchmark's clock around
``Session.submit`` alone, which runs ``resultcache.table_fingerprint`` on
the submitting thread."""

LAYER = "client / session"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    import statistics

    took = [r.submit_s for r in run.requests if r.error is None]
    return statistics.median(took) if took else None
