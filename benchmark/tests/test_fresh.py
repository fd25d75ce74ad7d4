"""The roll makes a table the server has not seen (a new fingerprint) with
the same rows, so the same q1 answer."""

import numpy as np


def test_roll_gives_new_bytes_and_the_same_answer():
    from spark_rapids_jni_tpu.runtime import resultcache

    from benchmark import reference_q1 as ref
    from benchmark import resolve

    maker = resolve.module("tables", "lineitem")
    base = maker.make(4096, 2**31 + 3)
    want = ref.q1(maker.host_copy(base))
    fresh = resolve.module("fresh", "roll").Freshener(base, seed=2**31 + 3)
    assert fresh.stride % 2 == 1 and np.gcd(fresh.stride, 4096) == 1
    prints = {resultcache.table_fingerprint(maker.to_table(base))}
    for _ in range(5):
        rolled = fresh.next()
        prints.add(resultcache.table_fingerprint(maker.to_table(rolled)))
        assert ref.compare(ref.q1(maker.host_copy(rolled)), want) == {
            "q1.int_mismatches": 0, "q1.avg_max_rel_err": 0.0}
    assert len(prints) == 6
    # the stride is drawn from the seed
    other = resolve.module("fresh", "roll").Freshener(base, seed=4)
    assert other.stride != fresh.stride


def test_same_seed_same_table():
    from benchmark import resolve

    maker = resolve.module("tables", "lineitem")
    a, b, c = (maker.host_copy(maker.make(1000, s)) for s in (9, 9, 10))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    # value ranges of the generator it copies (models/tpch.lineitem_table)
    assert a["l_quantity"].min() >= 100 and a["l_quantity"].max() < 5100
    assert set(np.unique(a["l_returnflag"])) <= set(b"ANR")
    assert set(np.unique(a["l_linestatus"])) <= set(b"FO")
