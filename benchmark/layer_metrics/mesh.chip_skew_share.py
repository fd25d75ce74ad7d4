"""Layer metric ``mesh.chip_skew_share``: the slowest chip's busy time less
the fastest's, over the slowest's, across the traced requests. Near 0 when
the four chips share the work, near 100 when one did it: a request is as
slow as its slowest chip."""

LAYER = "mesh"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "query_p50_s"
BETTER = "lower"


def read(run):
    from benchmark import mesh_reduce

    return mesh_reduce.chip_skew_share(run)
