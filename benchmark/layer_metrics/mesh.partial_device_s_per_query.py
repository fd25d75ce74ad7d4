"""Layer metric ``mesh.partial_device_s_per_query``: device time a request
under the ``partial`` stage of a groupby lowered over a mesh: a chip's own
sort-path aggregate of its rows, averaged over the chips."""

LAYER = "mesh"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import mesh_reduce

    return mesh_reduce.stage_seconds_per_query(run, "partial")
