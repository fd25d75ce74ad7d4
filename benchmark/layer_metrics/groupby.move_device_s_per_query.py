"""Layer metric ``groupby.move_device_s_per_query``: device time a request under
the ``move`` sub-scope of the plan's ``GroupBy`` nodes: the word-moving
sort path bringing what it reads at every row (the keys, the aggregated
columns, their validity and the row-valid bit, as packed 32-bit words)
into key order (``ops/sort.py permute`` / ``_move_words``)."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"
STAGE = "move"


def read(run):
    from benchmark import resolve

    return resolve.module(
        "layer_metrics", "groupby.key_sort_device_s_per_query"
    ).stage_seconds_per_query(run, STAGE)
