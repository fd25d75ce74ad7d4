"""Layer metric ``mesh.exchange_device_s_per_query``: device time a request
under the ``exchange`` stage of a groupby lowered over a mesh
(``hash_shuffle``: the packing of the real partial rows and the
``all_to_all`` over ICI), averaged over the chips."""

LAYER = "mesh"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"


def read(run):
    from benchmark import mesh_reduce

    return mesh_reduce.stage_seconds_per_query(run, "exchange")
