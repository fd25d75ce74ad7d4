"""Layer metric ``join.probe_device_s_per_query``: device time a request under the
``probe`` sub-scope of the plan's general joins (``fusion.Join``):
everything of the join but ordering the build side. For a semi or anti
join the runs' heads, the running maximum, the sort that brings the bits
back to the probe's rows and the mask; for a maps-based join the
searches, the prefix sum, the maps and their gathers."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"
STAGE = "probe"


def read(run):
    from benchmark import resolve

    return resolve.module(
        "layer_metrics", "join.build_device_s_per_query"
    ).stage_seconds_per_query(run, STAGE)
