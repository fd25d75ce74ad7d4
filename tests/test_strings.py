"""String relational-core tests: padded layout round trip, memcmp sort
order, groupby on string keys, and full variable-length XXH64 parity with
the independent host oracle (tests/xxh64_ref.py).

Mirrors the reference's oracle pattern (SURVEY.md section 4: round-trip /
golden-equality against the host representation): cuDF handles STRING keys
in sort/groupby/join (capability surface, reference build-libcudf.xml:34-60);
these tests pin the same behavior for the TPU substrate.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_jni_tpu import types as t
from spark_rapids_jni_tpu.columnar import Column, Table
from spark_rapids_jni_tpu.ops import strings as s
from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate
from spark_rapids_jni_tpu.ops.hash import table_xxhash64
from spark_rapids_jni_tpu.ops.sort import sort_table
from tests.xxh64_ref import xxh64


def random_strings(rng, n, max_len=20, alphabet=b"abcXYZ019 \x00\xc3\xa9"):
    out = []
    for _ in range(n):
        k = int(rng.integers(0, max_len + 1))
        out.append(bytes(rng.choice(list(alphabet), size=k)).decode("latin1"))
    return out


class TestPaddedLayout:
    def test_round_trip(self, rng):
        vals = ["", "a", "hello world", None, "abc\x00def", "x" * 31]
        col = Column.from_pylist(vals, t.STRING)
        padded = s.pad_strings(col)
        assert padded.is_padded_string
        assert padded.to_pylist() == vals
        back = s.unpad_strings(padded)
        assert not back.is_padded_string
        assert back.to_pylist() == vals

    def test_round_trip_random(self, rng):
        vals = random_strings(rng, 257)
        vals[13] = None
        col = Column.from_pylist(vals, t.STRING)
        assert s.unpad_strings(s.pad_strings(col)).to_pylist() == vals

    def test_empty_column(self):
        col = Column.from_pylist([], t.STRING)
        padded = s.pad_strings(col)
        assert padded.size == 0
        assert s.unpad_strings(padded).to_pylist() == []

    def test_gather(self, rng):
        vals = ["bb", "a", None, "ddd", ""]
        col = Column.from_pylist(vals, t.STRING)
        g = s.gather_strings(col, jnp.asarray([3, 0, 2, 1, 4, 0]))
        assert g.to_pylist() == ["ddd", "bb", None, "a", "", "bb"]


class TestStringSort:
    def test_memcmp_order(self, rng):
        vals = ["b", "ab", "", "abc", "a", "ab\x00", "aa", "B", None, "ab"]
        tbl = Table([
            Column.from_pylist(vals, t.STRING),
            Column.from_pylist(list(range(len(vals))), t.INT32),
        ])
        out = sort_table(tbl, keys=[0], nulls_first=[True])
        got = out.column(0).to_pylist()
        expect = [None] + sorted(v for v in vals if v is not None)
        assert got == expect

    def test_desc_nulls_last(self, rng):
        vals = random_strings(rng, 101)
        vals[7] = None
        tbl = Table([Column.from_pylist(vals, t.STRING)])
        out = sort_table(tbl, keys=[0], ascending=[False], nulls_first=[False])
        got = out.column(0).to_pylist()
        expect = sorted((v for v in vals if v is not None), reverse=True) + [None]
        assert got == expect

    def test_string_secondary_key(self, rng):
        k1 = ["x", "x", "y", "y", "x"]
        k2 = ["b", "a", "c", "a", "a"]
        tbl = Table([
            Column.from_pylist(k1, t.STRING),
            Column.from_pylist(k2, t.STRING),
            Column.from_pylist([0, 1, 2, 3, 4], t.INT32),
        ])
        out = sort_table(tbl, keys=[0, 1])
        assert out.column(2).to_pylist() == [1, 4, 0, 3, 2]


class TestStringGroupBy:
    def test_q1_style_string_keys(self, rng):
        # TPC-H q1 grouping shape on real STRING flags (VERDICT round-2 #2)
        n = 4000
        flags = ["A", "N", "R"]
        status = ["F", "O"]
        f = [flags[i] for i in rng.integers(0, 3, n)]
        st = [status[i] for i in rng.integers(0, 2, n)]
        qty = rng.integers(1, 50, n).astype(np.int64)
        tbl = Table([
            Column.from_pylist(f, t.STRING),
            Column.from_pylist(st, t.STRING),
            Column.from_numpy(qty),
        ])
        res = groupby_aggregate(tbl, keys=[0, 1], aggs=[(2, "sum"), (2, "count")])
        out = res.compact()
        got = {
            (out.column(0).to_pylist()[i], out.column(1).to_pylist()[i]):
                (out.column(2).to_pylist()[i], out.column(3).to_pylist()[i])
            for i in range(int(res.num_groups))
        }
        expect = {}
        for fi, si, qi in zip(f, st, qty):
            tot, cnt = expect.get((fi, si), (0, 0))
            expect[(fi, si)] = (tot + int(qi), cnt + 1)
        assert got == expect

    def test_null_string_group(self):
        vals = ["a", None, "a", None, "b"]
        x = [1, 2, 3, 4, 5]
        tbl = Table([
            Column.from_pylist(vals, t.STRING),
            Column.from_pylist(x, t.INT64),
        ])
        res = groupby_aggregate(tbl, [0], [(1, "sum")])
        out = res.compact()
        got = dict(zip(out.column(0).to_pylist(), out.column(1).to_pylist()))
        assert got == {None: 6, "a": 4, "b": 5}

    def test_max_groups_overflow_and_auto(self, rng):
        from spark_rapids_jni_tpu.ops.groupby import groupby_aggregate_auto

        n = 512
        keys = [f"k{i:03d}" for i in rng.integers(0, 100, n)]
        tbl = Table([
            Column.from_pylist(keys, t.STRING),
            Column.from_pylist([1] * n, t.INT64),
        ])
        small = groupby_aggregate(tbl, [0], [(1, "count")], max_groups=8)
        assert bool(small.overflowed)
        auto = groupby_aggregate_auto(tbl, [0], [(1, "count")],
                                      initial_max_groups=8)
        assert not bool(auto.overflowed)
        assert int(auto.num_groups) == len(set(keys))


class TestXXH64Bytes:
    @pytest.mark.parametrize("width", [8, 31, 32, 40, 100])
    def test_matches_reference_all_lengths(self, rng, width):
        # every length 0..width crosses each phase boundary of the algorithm
        # (empty / <4 / <8 / <32 / stripes+tails)
        raw = [bytes(rng.integers(0, 256, size=k, dtype=np.uint8))
               for k in range(width + 1)]
        n = len(raw)
        mat = np.zeros((n, width if width else 1), dtype=np.uint8)
        for i, b in enumerate(raw):
            mat[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
        lengths = np.array([len(b) for b in raw], dtype=np.int32)
        seeds = np.asarray(rng.integers(0, 1 << 63, size=n), dtype=np.uint64)
        got = np.asarray(
            s.xxhash64_bytes(jnp.asarray(mat), jnp.asarray(lengths),
                             jnp.asarray(seeds))
        )
        expect = np.array(
            [xxh64(b, seed=int(sd)) for b, sd in zip(raw, seeds)],
            dtype=np.uint64,
        )
        np.testing.assert_array_equal(got, expect)

    def test_table_hash_with_string_column(self, rng):
        vals = ["", "spark", "a longer string that crosses 32 bytes easily!",
                None, "xyz"]
        ints = [7, None, 9, 10, 11]
        tbl = Table([
            Column.from_pylist(ints, t.INT32),
            Column.from_pylist(vals, t.STRING),
        ])
        got = np.asarray(table_xxhash64(tbl)).astype(np.uint64)
        # host oracle: chain per column, null passes seed through
        expect = []
        for iv, sv in zip(ints, vals):
            h = 42
            if iv is not None:
                h = xxh64(int(np.int32(iv)).to_bytes(4, "little", signed=True),
                          seed=h)
            if sv is not None:
                h = xxh64(sv.encode(), seed=h)
            expect.append(h)
        np.testing.assert_array_equal(got, np.array(expect, dtype=np.uint64))


class TestReviewRegressions:
    def test_empty_table_groupby_with_max_groups(self):
        tbl = Table([
            Column.from_pylist([], t.STRING),
            Column.from_pylist([], t.INT64),
        ])
        res = groupby_aggregate(tbl, [0], [(1, "sum")], max_groups=4)
        assert int(res.num_groups) == 0
        assert not bool(res.overflowed)

    def test_compact_on_overflow_raises(self, rng):
        tbl = Table([
            Column.from_pylist(["a", "b", "c"], t.STRING),
            Column.from_pylist([1, 2, 3], t.INT64),
        ])
        res = groupby_aggregate(tbl, [0], [(1, "sum")], max_groups=2)
        assert bool(res.overflowed)
        with pytest.raises(ValueError, match="overflowed"):
            res.compact()

    def test_jit_over_padded_strings(self):
        import jax

        col = s.pad_strings(Column.from_pylist(["b", "a", "c"], t.STRING))
        tbl = Table([col])

        @jax.jit
        def run(tb):
            from spark_rapids_jni_tpu.ops.sort import sort_table

            return sort_table(tb, [0])

        out = run(tbl)
        assert out.column(0).to_pylist() == ["a", "b", "c"]

    def test_pad_inside_jit_without_width_raises(self):
        import jax

        col = Column.from_pylist(["b", "a"], t.STRING)

        @jax.jit
        def run(c):
            return s.pad_strings(c).data

        with pytest.raises(ValueError, match="static width"):
            run(col)


class TestStringMinMax:
    def test_min_max_matches_oracle(self, rng):
        n = 400
        keys = [int(v) for v in rng.integers(0, 12, n)]
        words = [f"w{v:03d}" for v in rng.integers(0, 500, n)]
        for i in range(0, n, 23):
            words[i] = None
        tbl = Table([
            Column.from_pylist(keys, t.INT32),
            Column.from_pylist(words, t.STRING),
        ])
        res = groupby_aggregate(tbl, [0], [(1, "min"), (1, "max")])
        out = res.compact()
        got = {
            out.column(0).to_pylist()[i]: (
                out.column(1).to_pylist()[i], out.column(2).to_pylist()[i])
            for i in range(int(res.num_groups))
        }
        want = {}
        for k, w in zip(keys, words):
            lo, hi = want.get(k, (None, None))
            if w is not None:
                lo = w if lo is None else min(lo, w)
                hi = w if hi is None else max(hi, w)
            want[k] = (lo, hi)
        assert got == want

    def test_all_null_group_is_null(self):
        tbl = Table([
            Column.from_pylist([1, 1, 2], t.INT32),
            Column.from_pylist([None, None, "z"], t.STRING),
        ])
        res = groupby_aggregate(tbl, [0], [(1, "min")])
        out = res.compact()
        assert out.column(1).to_pylist() == [None, "z"]


# ---- search predicates -----------------------------------------------------


def _rand_strings(rng, n, alphabet="abc%_x", maxlen=12):
    out = []
    for _ in range(n):
        ln = int(rng.integers(0, maxlen))
        out.append("".join(rng.choice(list(alphabet)) for _ in range(ln)))
    return out


def test_contains_starts_ends_vs_python(rng):
    from spark_rapids_jni_tpu.ops import strings as s

    vals = _rand_strings(rng, 300) + [None, "", "abc"]
    col = Column.from_pylist(vals, t.STRING)
    for needle in ["a", "ab", "abc", "", "bca", "xxxxxxxxxxxxxxxxx"]:
        got_c = s.contains(col, needle).to_pylist()
        got_s = s.starts_with(col, needle).to_pylist()
        got_e = s.ends_with(col, needle).to_pylist()
        for i, v in enumerate(vals):
            if v is None:
                assert got_c[i] is None and got_s[i] is None
                continue
            assert got_c[i] == (needle in v), (v, needle)
            assert got_s[i] == v.startswith(needle), (v, needle)
            assert got_e[i] == v.endswith(needle), (v, needle)


def test_like_vs_regex_oracle(rng):
    import re

    from spark_rapids_jni_tpu.ops import strings as s

    def like_re(pat):
        out = []
        i = 0
        while i < len(pat):
            c = pat[i]
            if c == "\\" and i + 1 < len(pat):
                out.append(re.escape(pat[i + 1]))
                i += 2
                continue
            if c == "%":
                out.append(".*")
            elif c == "_":
                out.append(".")
            else:
                out.append(re.escape(c))
            i += 1
        return re.compile("".join(out), re.DOTALL)

    vals = _rand_strings(rng, 250) + ["", "abc", "a%b", "axxb", None]
    col = Column.from_pylist(vals, t.STRING)
    patterns = ["%", "", "a%", "%a", "%ab%", "a_c", "_", "__", "a%b%c",
                "abc", "%abc", "abc%", "a\\%b", "%a_c%", "a%%b", "_%_"]
    for pat in patterns:
        rx = like_re(pat)
        got = s.like(col, pat).to_pylist()
        for i, v in enumerate(vals):
            if v is None:
                assert got[i] is None
                continue
            want = rx.fullmatch(v) is not None
            assert got[i] == want, (v, pat, got[i], want)


def test_like_underscore_multibyte_utf8_char_semantics():
    """'_' matches one CHARACTER, not one byte (Spark semantics) —
    multi-byte UTF-8 no longer fails loudly, it works."""
    from spark_rapids_jni_tpu.ops import strings as s

    col = Column.from_pylist(["aéc", "abc", "axyc", "日本語"], t.STRING)
    assert s.like(col, "a_c").to_pylist() == [True, True, False, False]
    assert s.like(col, "___").to_pylist() == [True, True, False, True]
    assert s.like(col, "_本_").to_pylist() == [False, False, False, True]
    assert s.like(col, "__").to_pylist() == [False, False, False, False]
    assert s.like(col, "_%").to_pylist() == [True, True, True, True]
    # '%' and literal patterns stay byte-exact on the same data
    assert s.like(col, "a%c").to_pylist() == [True, True, True, False]
    assert s.contains(col, "é").to_pylist() == [True, False, False, False]


def test_like_multibyte_vs_regex_oracle(rng):
    """Random UTF-8 strings x '_'-bearing patterns against Python's
    character-level regex engine."""
    import re

    from spark_rapids_jni_tpu.ops import strings as s

    alphabet = list("abéλ日x")
    vals = ["".join(rng.choice(alphabet,
                               size=int(rng.integers(0, 7))))
            for _ in range(200)]
    col = Column.from_pylist(vals, t.STRING)
    for pat in ["_", "__", "a_", "_é", "%_", "_%_", "a_%", "%日_",
                "___%", "_b_"]:
        rx = re.compile(
            "".join(".*" if c == "%" else "." if c == "_"
                    else re.escape(c) for c in pat), re.DOTALL)
        got = s.like(col, pat).to_pylist()
        for v, g in zip(vals, got):
            want = rx.fullmatch(v) is not None
            assert g == want, (v, pat, g, want)


def _like_rows_numpy(chars, lengths, segs, gaps, tail_gap):
    """``_like_rows``' compiled plan evaluated plainly: the reachable end
    positions as booleans, a floating gap as ``np.logical_or.accumulate``."""
    n, w = chars.shape
    jdx = np.arange(w + 1)
    within = jdx[None, :] <= lengths[:, None]
    edge = np.ones((n, 1), bool)
    is_b = np.concatenate(
        [edge, (chars[:, 1:] & 0xC0) != 0x80, edge], axis=1)
    upto = np.maximum.accumulate(np.where(is_b, jdx[None, :], -1), axis=1)
    prev_b = np.concatenate([np.full((n, 1), -1), upto[:, :-1]], axis=1)

    def advance(reach, chars_on):
        for _ in range(chars_on):
            reach = is_b & (prev_b >= 0) & np.take_along_axis(
                reach, np.clip(prev_b, 0, w), axis=1)
        return reach

    reach = np.zeros((n, w + 1), bool)
    reach[:, 0] = True
    for seg, (mincnt, floating) in zip(segs, gaps):
        reach = advance(reach, mincnt) & within
        if floating:
            reach = np.logical_or.accumulate(reach, axis=1)
        if seg:
            f = len(seg)
            moved = np.zeros_like(reach)
            for j in range(w + 1 - f):
                moved[:, j + f] = reach[:, j] & (j + f <= lengths) & np.all(
                    chars[:, j:j + f] == np.frombuffer(seg, np.uint8), axis=1)
            reach = moved
    mincnt, floating = tail_gap
    reach = advance(reach, mincnt) & within
    if floating:
        return reach.any(axis=1)
    return reach[np.arange(n), lengths]


@pytest.mark.parametrize(
    "pattern", ["%a%b%", "%a_b%", "a%b", "%a%_", "%本%c", "%%a"])
def test_like_rows_equal_the_plain_scan_of_the_same_plan(
        pattern, rng, monkeypatch):
    """A floating gap carried as its first position answers what the
    prefix-or along the positions answers: before a literal, before a '_'
    gap, before an anchored tail, around a multibyte literal; rows shorter
    than the needle, empty rows and rows of the full width among them."""
    alphabet = list("ab本c")
    vals = ["".join(rng.choice(alphabet, size=int(rng.integers(0, 9))))
            for _ in range(400)]
    vals += ["", "", "a", "b", "本", "ab", "abababab", "本本本本本本本本"]
    calls = []
    rows = s._like_rows

    def spy(chars, lengths, *plan):
        calls.append((np.asarray(chars), np.asarray(lengths), plan))
        return rows(chars, lengths, *plan)

    monkeypatch.setattr(s, "_like_rows", spy)
    got = np.asarray(s.like(Column.from_pylist(vals, t.STRING), pattern).data)
    (chars, lengths, plan), = calls
    assert chars.shape[1] == lengths.max() == 24 and lengths.min() == 0
    want = _like_rows_numpy(chars, lengths, *plan)
    assert np.array_equal(got.astype(bool), want)
    assert 0 < want.sum() < len(vals)


def test_like_floating_gap_lowers_to_no_scan():
    """q13's pattern holds no prefix scan along the positions: an
    ``associative_scan`` unrolls into levels of ``pad`` and ``or`` equations
    (24 and 36 for this pattern's two), none of which XLA:TPU fuses."""
    import jax

    plan = ((b"special", b"requests"), ((0, True), (0, True)), (0, True))
    closed = jax.make_jaxpr(lambda c, n: s._like_rows(c, n, *plan))(
        jax.ShapeDtypeStruct((256, 79), jnp.uint8),
        jax.ShapeDtypeStruct((256,), jnp.int32))
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            names.append(eqn.primitive.name)
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(closed.jaxpr)
    assert names.count("reduce_min") == 2
    assert not {"pad", "or"} & set(names)


def test_like_invalid_escape_patterns_raise():
    """Spark's checkLikePattern posture: the escape char must precede
    '%', '_', or itself; a trailing escape or escape of an ordinary char
    is an invalid pattern, not a silent literal (ADVICE r3)."""
    from spark_rapids_jni_tpu.ops import strings as s

    col = Column.from_pylist(["abc\\", "abc"], t.STRING)
    for bad in ["abc\\", "\\", "a\\bc", "%\\x"]:
        with pytest.raises(ValueError, match="escape"):
            s.like(col, bad)
    # the three legal escape targets still work
    assert s.like(col, "abc\\\\").to_pylist() == [True, False]
    assert s.like(col, "ab\\%").to_pylist() == [False, False]
    assert s.like(col, "ab\\_").to_pylist() == [False, False]


def test_predicates_keep_validity_none_fast_path():
    from spark_rapids_jni_tpu.ops import strings as s

    col = Column.from_pylist(["ab", "cd"], t.STRING)
    assert col.validity is None
    assert s.contains(col, "a").validity is None
    assert s.like(col, "%a%").validity is None


def test_substring_vs_python(rng):
    from spark_rapids_jni_tpu.ops import strings as s

    vals = _rand_strings(rng, 200, alphabet="abcdef", maxlen=10) + ["", None]
    col = Column.from_pylist(vals, t.STRING)
    for start, ln in [(0, 3), (2, None), (5, 2), (-3, 2), (-1, None),
                      (0, 0), (9, 5), (-20, 3), (-20, None)]:
        got = unpad(s.substring(col, start, ln))
        for i, v in enumerate(vals):
            if v is None:
                assert got[i] is None
                continue
            if start < 0:
                # Spark substringSQL: end from the UNCLAMPED position
                raw = len(v) + start
                b = max(raw, 0)
                e = len(v) if ln is None else min(max(raw + ln, 0), len(v))
                want = v[b:e] if e > b else ""
            else:
                want = v[start:] if ln is None else v[start:start + ln]
            assert got[i] == want, (v, start, ln, got[i], want)


def unpad(col):
    from spark_rapids_jni_tpu.ops.strings import unpad_strings

    return unpad_strings(col).to_pylist()


def test_upper_lower_ascii_and_guard():
    from spark_rapids_jni_tpu.ops import strings as s

    col = Column.from_pylist(["aBc9!", "", None, "XYZ"], t.STRING)
    assert unpad(s.upper(col)) == ["ABC9!", "", None, "XYZ"]
    assert unpad(s.lower(col)) == ["abc9!", "", None, "xyz"]
    # non-ASCII no longer fails loudly: host Unicode engine takes over
    assert s.upper(Column.from_pylist(["é"], t.STRING)).to_pylist() == ["É"]


def test_upper_lower_non_ascii_host_fallback():
    """Non-ASCII no longer fails loudly: it routes through the host
    Unicode engine (Java Locale.ROOT behavior, incl. one-to-many like
    ß -> SS)."""
    from spark_rapids_jni_tpu.ops import strings as s

    col = Column.from_pylist(["Straße", "ΣΊΓΜΑ", "abC", None], t.STRING)
    assert s.upper(col).to_pylist() == ["STRASSE", "ΣΊΓΜΑ", "ABC", None]
    assert s.lower(col).to_pylist() == ["straße", "σίγμα", "abc", None]
    # pure-ASCII columns still take the vectorized path (chars stay bytes)
    a = Column.from_pylist(["Mixed", "CASE"], t.STRING)
    assert s.upper(a).to_pylist() == ["MIXED", "CASE"]


def test_regexp_contains_extract_replace():
    from spark_rapids_jni_tpu.ops import strings as s

    col = Column.from_pylist(
        ["foo123bar", "nope", "a99", None, ""], t.STRING)
    got = s.regexp_contains(col, r"\d+").to_pylist()
    assert got == [True, False, True, None, False]

    ext = s.regexp_extract(col, r"([a-z]+)(\d+)", 2).to_pylist()
    assert ext == ["123", "", "99", None, ""]

    rep = s.regexp_replace(col, r"(\d+)", "<$1>").to_pylist()
    assert rep == ["foo<123>bar", "nope", "a<99>", None, ""]

    # literal dollar via Java escape
    rep2 = s.regexp_replace(col, r"\d+", "\\$").to_pylist()
    assert rep2 == ["foo$bar", "nope", "a$", None, ""]


def test_regexp_java_semantics_edges():
    from spark_rapids_jni_tpu.ops import strings as s

    # $10 with two groups: Java binds greedily but only to VALID group
    # numbers -> 10 > 2 stops the scan, so $1 ('a') then literal '0'
    col = Column.from_pylist(["a123"], t.STRING)
    assert s.regexp_replace(col, r"([a-z])(\d+)", "$10").to_pylist() == \
        ["a0"]
    # \n in a Java replacement is the LITERAL letter n, not a newline
    assert s.regexp_replace(col, r"\d+", "\\n").to_pylist() == ["an"]
    # \d is ASCII [0-9] like java.util.regex, not Unicode digits
    arabic = Column.from_pylist(["٣", "3"], t.STRING)
    assert s.regexp_contains(arabic, r"\d").to_pylist() == [False, True]
    # group number beyond the pattern's groups fails loudly
    with pytest.raises(ValueError, match="group"):
        s.regexp_replace(col, r"(\d+)", "$7")
    # possessive quantifiers compile natively (Python 3.11+ re supports
    # Java's *+ semantics)
    assert s.regexp_contains(
        Column.from_pylist(["aaab", "aaa"], t.STRING), r"a*+b"
    ).to_pylist() == [True, False]


def test_regexp_rejects_java_class_syntax_and_bad_groups():
    from spark_rapids_jni_tpu.ops import strings as s

    col = Column.from_pylist(["ab"], t.STRING)
    with pytest.raises(ValueError, match="intersection"):
        s.regexp_contains(col, r"[a-c&&[b]]")
    with pytest.raises(ValueError, match="nested"):
        s.regexp_contains(col, r"[a[b]]")
    # escaped brackets and class-internal literals stay fine
    assert s.regexp_contains(col, r"[ab]\[?").to_pylist() == [True]
    assert s.regexp_contains(col, r"a&&?b").to_pylist() == [False]
    with pytest.raises(ValueError, match="out of range"):
        s.regexp_extract(col, r"(\w)", 2)
