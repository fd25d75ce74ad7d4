"""Layer metric ``groupby.key_sort_device_s_per_query``: device time a request
under the ``key_sort`` sub-scope of the plan's ``GroupBy`` nodes: the
word-moving sort path's order by the key words (``ops/sort.py``: one
variadic sort of a key of one or two words, a stable sort a word with a
gather of the word by the running order for a wider one) and the words
brought into it. A groupby that takes its aggregates in place names no
such scope."""

LAYER = "operators"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "rows_per_s"
BETTER = "lower"
STAGE = "key_sort"


def stage_seconds_per_query(run, stage: str):
    """Device seconds a request under ``region.<plan>/<a GroupBy's
    label>/.../<stage>``: the union of those operations over the traced
    requests' number (``scope_reduce.seconds_by_node``). A region's
    operations run only while a request is open (the warm-up ends before
    the trace starts, and the trace stops between requests), so the union
    needs no clipping to the requests. ``None`` without a trace, on a
    program whose groupbys name no such scope, or in a mix without a
    ``GroupBy``."""
    import re

    from benchmark import scope_reduce, span_reduce
    from benchmark.trace_reduce import total, union

    kinds = scope_reduce._node_kinds(run)
    found = scope_reduce.seconds_by_node(run)
    labels = [re.escape(scope) for scope, kind in (kinds or {}).items()
              if kind in scope_reduce.GROUPBYS]
    if not labels or found is None:
        return None
    under = re.compile(
        r"(?:^|/)region\.[^/]+/(?:" + "|".join(labels) + r")/(?:[^/:]+/)*"
        + re.escape(stage) + r"(?:[/:]|$)")
    spans = [(start, end) for start, end, _, scope
             in scope_reduce.device_operations(
                 span_reduce.trace_path(run), run.device["platform"])
             if scope and under.search(scope)]
    if not spans:
        return None
    return total(union(spans)) / 1e9 / found[0]


def read(run):
    return stage_seconds_per_query(run, STAGE)
