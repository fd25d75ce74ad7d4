"""Loop ``closed``: each client sends its next request only when the last
one has come back, as a Spark task waits for its batch's result before it
asks again. One client today; the mix's ``clients`` says how many."""

from __future__ import annotations

import time


def run(mix: dict, seconds: float, request, stop_trace=None) -> None:
    """Call ``request(i)`` one after another until ``seconds`` have passed;
    a request that has started when the window closes is finished and
    counted. ``stop_trace(i, elapsed)``, where given, is asked after each
    request whether the traced part is over."""
    if int(mix.get("clients", 1)) != 1:
        raise ValueError(
            "loop 'closed' drives one client; a mix with more brings a "
            "loop of its own under benchmark/loops/")
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        request(i)
        i += 1
        if stop_trace is not None:
            stop_trace(i, time.perf_counter() - t0)
