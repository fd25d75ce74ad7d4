"""Plan ``q3_planned``: TPC-H q3 with the dense clustered primary keys of
customer and orders declared, as one fused region: two positional joins, a
groupby of about one group in seventy lineitem rows on the sort path, and
a two-key sort of the result.

It scans three tables. ``customer`` and ``orders`` are the joins' build
sides: resident, bound to every request as the same device arrays (the
positional join needs them in load order, which a roll would break).
``lineitem`` is the probe side and new every request."""

from benchmark.reference_q3 import (  # noqa: F401  (the plan's interface)
    CUTOFF_DAYS,
    LIMITS,
    SEGMENT,
    compare,
    control,
    min_bytes,
    oracle,
    read_answer,
)

# The configuration's guarantee ``declarations`` (a request whose joins saw
# a key outside the declared dense primary keys, or whose groupby passed
# its bound, is a failed request) needs a server that reads what the plan's
# nodes report: a program without ``fusion.meta_facts`` would resolve such
# a request as served, so this cell does not run on it (an ImportError
# before any table is made).
from spark_rapids_jni_tpu.runtime.fusion import meta_facts  # noqa: E402,F401

BINDINGS = {"customer": "customer", "orders": "orders",
            "lineitem": "lineitem"}      # the plan's scan -> the config's table
FRESH = ("lineitem",)                    # rolled for every request


def plan():
    from spark_rapids_jni_tpu.models import tpch

    return tpch._q3_planned_plan(SEGMENT, CUTOFF_DAYS)
