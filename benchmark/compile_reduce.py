"""What ``runtime/dispatch.py`` observes of a compile, for the readers of
``dispatch.compile_*``, ``region.hbm_*`` and ``admission.reserved_need_share``.

- **Where a compile's seconds went** is read from the process's
  ``dispatch.xla.*`` counters, absolute and not the window's delta: the
  warm-up's compiles lie before the window, and in a cell of more than
  about 1,700 requests the ring has dropped their spans before a reader
  runs. A program that writes none (any commit before them) gives ``None``.
- **What an executable needs of the HBM** rides on every
  ``dispatch.execute`` span (``need_bytes``, ``temp_bytes``: XLA's own
  buffer assignment, one chip's share over a mesh), and what admission
  reserved for the request on its ``admission.wait`` span
  (``estimate_bytes``); the two meet here by ``request``, through
  ``span_reduce`` as every span-fed reader, so a window that overran the
  ring is reduced the same way.
"""

from __future__ import annotations

import statistics

from benchmark import span_reduce

_XLA = "dispatch.xla."


def xla_counters():
    """The process's ``dispatch.xla.*`` counters by their last name, or
    ``None`` for a program that has written none."""
    from spark_rapids_jni_tpu.telemetry import REGISTRY

    return {k[len(_XLA):]: v
            for k, v in REGISTRY.counters(_XLA).items()} or None


def seconds(name: str):
    """The counter ``dispatch.xla.<name>_ns`` in seconds."""
    found = xla_counters()
    if found is None or f"{name}_ns" not in found:
        return None
    return found[f"{name}_ns"] / 1e9


def region_needs(run):
    """One entry a held request that ran an executable which says what it
    needs: ``{"need": the largest need_bytes among the request's
    dispatch.execute spans, "temp": that executable's temp_bytes,
    "reserved": the estimate_bytes of its admission.wait or None}``.
    ``None`` where no request says so."""
    if not hasattr(run, "_region_needs"):
        needs = []
        for req in span_reduce.window_requests(run) or ():
            ran = [r for r in req["spans"]
                   if r["op"] == "dispatch.execute" and r.get("need_bytes")]
            if not ran:
                continue
            top = max(ran, key=lambda r: r["need_bytes"])
            reserved = [r["estimate_bytes"] for r in req["spans"]
                        if r["op"] == "admission.wait"
                        and r.get("estimate_bytes") is not None]
            needs.append({"need": top["need_bytes"],
                          "temp": top.get("temp_bytes", 0),
                          "reserved": reserved[0] if reserved else None})
        run._region_needs = needs or None
    return run._region_needs


def median_of_needs(run, value):
    """Median over :func:`region_needs` of ``value(entry)``, the entries
    it gives ``None`` for left out; ``None`` where nothing is left."""
    values = [v for v in map(value, region_needs(run) or ()) if v is not None]
    return statistics.median(values) if values else None
