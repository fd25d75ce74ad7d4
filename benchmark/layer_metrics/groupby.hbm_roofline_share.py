"""Layer metric ``groupby.hbm_roofline_share``: the sort-path groupby's share of
its roofline: the least time the chip could take to read what the
groupbys must read and write what they must write, over the device time
under the ``GroupBy`` nodes' scopes (``groupby.device_s_per_query``). What
they must move a request is ``groupby_bytes`` below: the key columns and
the aggregated columns, each with a byte of validity, of every real row
that entered (counter ``groupby.read_bytes``: ``groupby.rows_in`` times
those bytes, 18 B a row for q18's ``order_qty``), and the same bytes a
row for every group found (``groupby.groups``): counters that count the
same rows whatever implements the groupby. The bound is HBM bandwidth
(``peaks.json``): one pass over those bytes. Not reported where no
sort-path groupby ran or no device time was found under one."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "higher"


def groupby_bytes(counters: dict, requests: int) -> float:
    """Bytes a request's sort-path groupbys must read and write: the
    counters are the window's, so their sum is shared out over its
    requests; a group is written at the mean width of the rows read."""
    rows = counters.get("groupby.rows_in", 0)
    if not requests or not rows:
        return 0.0
    read = counters.get("groupby.read_bytes", 0)
    written = counters.get("groupby.groups", 0) * read / rows
    return (read + written) / requests


def read(run):
    from benchmark import resolve

    if (not run.counters.get("groupby.rows_in")
            or "hbm_bytes_per_s" not in run.peaks):
        return None
    seconds = resolve.module(
        "layer_metrics", "groupby.device_s_per_query").read(run)
    if not seconds:
        return None
    least = groupby_bytes(run.counters, len(run.requests)) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
