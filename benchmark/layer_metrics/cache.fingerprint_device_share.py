"""Layer metric ``cache.fingerprint_device_share``: of the bytes fingerprinted
in the window (``cache.fingerprint_bytes``), the share digested on the device
where they live (``cache.fingerprint_device_bytes``); the rest came to the
host. Nothing for a program that has never written the second counter, or in
a window that fingerprinted nothing."""

LAYER = "result cache"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "query_p50_s"
BETTER = "higher"


def read(run):
    from benchmark import span_reduce

    on_device = span_reduce.counter_per_request(
        run, "cache.fingerprint_device_bytes")
    whole = span_reduce.counter_per_request(run, "cache.fingerprint_bytes")
    if on_device is None or not whole:
        return None
    return 100.0 * on_device / whole
