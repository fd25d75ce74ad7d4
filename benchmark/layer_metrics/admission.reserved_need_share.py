"""Layer metric ``admission.reserved_need_share``: what admission reserved
for a request over what its largest executable needs: the
``estimate_bytes`` of its ``admission.wait`` span over its
``region.hbm_need_bytes``, median over the window's held requests. Under
100 the door admits what it has not reserved for; over 100 it reserves
more than the region needs and turns away requests that would fit."""

LAYER = "admission"
UNIT = "%"
SOURCE = "program_span"
MOVES = "query_p50_s"
BETTER = "higher"


def read(run):
    from benchmark import compile_reduce

    return compile_reduce.median_of_needs(
        run, lambda e: None if e["reserved"] is None
        else 100.0 * e["reserved"] / e["need"])
