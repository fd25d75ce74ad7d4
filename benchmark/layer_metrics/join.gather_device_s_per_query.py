"""Layer metric ``join.gather_device_s_per_query``: device time a request
under the ``gather_rows`` sub-scope of the plan's general joins
(``fusion.Join`` of a ``how`` that lays rows out): ``apply_join_maps``,
the columns of both sides fetched by the join's maps, apart from making
the maps (``build`` and ``probe``). The scope is not named ``gather``:
that is XLA's name for the primitive too and ends the op name of every
gather under ``probe``, which a reader by scope would then count as well
(the first traced run read 1.60 s so, beside a join of 1.85). Not
reported on a program whose joins name no such scope."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"
STAGE = "gather_rows"


def read(run):
    from benchmark import resolve

    return resolve.module(
        "layer_metrics", "join.build_device_s_per_query"
    ).stage_seconds_per_query(run, STAGE)
