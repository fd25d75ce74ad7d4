"""Layer metric ``mesh.exchange_hbm_roofline_share``: the exchange's share
of its roofline: the least time a chip could take to move what the
exchange must move, over the device time a request spends under the
``exchange`` stage averaged over the chips
(``mesh.exchange_device_s_per_query``). What it must move a request is
``exchange_bytes`` below: every real row that entered a shuffle is read
once where it lies and written once where it lands, the columns that rode
and a validity byte each (counter ``shuffle.read_bytes``: a count of the
same rows whatever implements the exchange), a chip doing its share. The
bound is HBM bandwidth (``peaks.json``); the interconnect's is not in that
file, so the least time is a floor and the share can only read low. Not
reported where no join was exchanged or no device time lies under the
stage."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "higher"


def exchange_bytes(counters: dict, requests: int, chips: int) -> float:
    """Bytes ONE chip must move a request for the exchanges of the joins
    lowered over the mesh: its share of the rows that entered a shuffle,
    read once and written once. The counters are the window's, so their
    sum is shared out over its requests."""
    if not requests or not chips:
        return 0.0
    return 2.0 * counters.get("shuffle.read_bytes", 0) / requests / chips


def read(run):
    from benchmark import resolve

    if (not run.counters.get("shuffle.read_bytes")
            or "hbm_bytes_per_s" not in run.peaks):
        return None
    seconds = resolve.module(
        "layer_metrics", "mesh.exchange_device_s_per_query").read(run)
    if not seconds:
        return None
    least = exchange_bytes(run.counters, len(run.requests),
                           int(run.workload["chips"])) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
