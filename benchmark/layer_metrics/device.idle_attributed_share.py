"""Layer metric ``device.idle_attributed_share``: of the device's idle time inside
the traced requests, the share that lies under a program span below the
roots ``submit.<plan>`` / ``query.<plan>``: idle time the program can name.
The rest is the client waiting for the result, and root self time."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "query_p50_s"
BETTER = "higher"


def read(run):
    from benchmark import span_reduce

    reduced = span_reduce.device(run)
    if reduced is None or not reduced["program_spans"] or not reduced[
            "idle_s"]:
        return None
    return 100.0 * reduced["idle_attributed_s"] / reduced["idle_s"]
