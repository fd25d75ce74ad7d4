"""The ``scan.*`` readers' shared step: a request's time under the spans a
file-backed binding writes (``spark_rapids_jni_tpu/parquet/split.py``),
through ``span_reduce`` as every span-fed reader, and nothing (``None``,
the metric left out of the line) for a program or a cell whose requests
write no such span."""

from __future__ import annotations

from benchmark import span_reduce


def median_of(run, *names: str):
    """``span_reduce.median_of_spans`` of ``names``, or ``None`` where no
    request of the window holds a span of any of them."""
    requests = span_reduce.window_requests(run)
    if requests is None or not any(
            r["op"] in names for req in requests for r in req["spans"]):
        return None
    return span_reduce.median_of_spans(run, *names)
