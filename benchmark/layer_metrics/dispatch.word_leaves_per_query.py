"""Layer metric ``dispatch.word_leaves_per_query``: 64-bit integer leaves a
request's pads handed to their executables as two uint32 planes, low word
and high word, and not as an int64 buffer the chip would split on the way
in (counter ``dispatch.pad.word_leaves`` over requests): the columns whose
``X64Combine`` the pad and whose two ``X64Split`` passes the region are
spared. 0 where every group sits on its bucket and over a mesh."""

LAYER = "dispatch"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "rows_per_s"
BETTER = "higher"


def read(run):
    from benchmark import span_reduce

    return span_reduce.counter_per_request(run, "dispatch.pad.word_leaves")
