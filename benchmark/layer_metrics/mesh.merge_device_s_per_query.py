"""Layer metric ``mesh.merge_device_s_per_query``: device time a request
under the ``merge`` and ``collect`` stages of a groupby lowered over a mesh
(the groupby of what a chip owns after the shuffle, the ``all_gather`` of
every chip's groups and their compaction), averaged over the chips."""

LAYER = "mesh"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import mesh_reduce

    return mesh_reduce.stage_seconds_per_query(run, "merge", "collect")
