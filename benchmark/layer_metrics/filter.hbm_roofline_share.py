"""Layer metric ``filter.hbm_roofline_share``: the string predicate's share of its
roofline: the least time the chip could take to read what the predicate
must read, over the device time under the filter's scope. What it must
read a request is ``like_bytes`` below: the comment bytes of every real
row at the column's width (counter ``strings.like_bytes``) and the 4 bytes
of each row's length (counter ``filter.rows_in``). The bound is HBM
bandwidth (``peaks.json``): one pass over the bytes as stored. Not reported
where no string predicate ran or no device time was found under it."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "higher"
LENGTH_BYTES = 4     # a padded string's length, int32


def like_bytes(counters: dict, requests: int) -> float:
    """Bytes a request's string predicates must read: the counters are the
    window's, so their sum is shared out over its requests."""
    if not requests:
        return 0.0
    return (counters.get("strings.like_bytes", 0)
            + LENGTH_BYTES * counters.get("filter.rows_in", 0)) / requests


def read(run):
    from benchmark import resolve

    if (not run.counters.get("strings.like_bytes")
            or "hbm_bytes_per_s" not in run.peaks):
        return None
    seconds = resolve.module(
        "layer_metrics", "filter.device_s_per_query").read(run)
    if not seconds:
        return None
    least = like_bytes(run.counters, len(run.requests)) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
