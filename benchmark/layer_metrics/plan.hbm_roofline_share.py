"""Layer metric ``plan.hbm_roofline_share``: the least time the chip could take for
the traced requests (each plan's ``min_bytes(rows)`` over the peak HBM
bytes/s of ``peaks.json``) over the time the device was busy with them. The
bound is HBM bandwidth: the MXU is idle in this workload. Not reported when
the busy time is 0."""

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "higher"


def read(run):
    trace = run.trace
    if (trace is None or not trace["busy_s"]
            or "hbm_bytes_per_s" not in run.peaks):
        return None
    traced = run.requests[:trace["requests"]]
    least = sum(run.plans[r.plan].min_bytes(r.rows) for r in traced)
    return 100.0 * (least / run.peaks["hbm_bytes_per_s"]) / trace["busy_s"]
