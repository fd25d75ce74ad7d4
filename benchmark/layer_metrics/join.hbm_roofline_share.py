"""Layer metric ``join.hbm_roofline_share``: the general join's share of its
roofline: the least time the chip could take to read what the join must
read, over the device time under the join nodes' scopes
(``join.device_s_per_query``). What it must read a request is
``join_bytes`` below: the key and its validity, 9 bytes, of every real
build row with a non-null key that entered the join (counter
``join.build_rows``) and of every real probe row (``join.probe_rows``):
counters that count the same rows whatever implements the join. The bound
is HBM bandwidth (``peaks.json``): one pass over those bytes. Not reported
where no general join ran or no device time was found under one."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "higher"
KEY_BYTES = 9        # a 64-bit key and its validity


def join_bytes(counters: dict, requests: int) -> float:
    """Bytes a request's general joins must read: the counters are the
    window's, so their sum is shared out over its requests."""
    if not requests:
        return 0.0
    return KEY_BYTES * (counters.get("join.build_rows", 0)
                        + counters.get("join.probe_rows", 0)) / requests


def read(run):
    from benchmark import resolve

    if (not run.counters.get("join.build_rows")
            or "hbm_bytes_per_s" not in run.peaks):
        return None
    seconds = resolve.module(
        "layer_metrics", "join.device_s_per_query").read(run)
    if not seconds:
        return None
    least = join_bytes(run.counters, len(run.requests)) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
