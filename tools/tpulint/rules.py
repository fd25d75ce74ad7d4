"""The twenty-two per-file tpulint rules.

Each rule encodes an invariant the stack already relies on implicitly;
the docstring of each ``check_*`` names the bug class that motivated it
(ADVICE.md round-5 findings, the r02 measurement reconciliation). Rules here
are pure-AST heuristics judging one file at a time: they
under-approximate anything that spans modules and occasionally
over-approximate (a reviewed-legitimate site carries a
``# tpulint: disable=<rule>`` pragma that doubles as documentation).
Cross-module properties — lock ordering, blocking calls reached through
call chains, guard inference over a class's access sites — are NOT in
scope for these rules; they belong to the whole-program rules in
``tools/tpulint/concurrency.py``, which run on the
``tools/tpulint/flows.py`` engine (one parse of the entire corpus, a
module-level call graph, a lock registry, and held-set propagation
through ``with`` blocks and intra-package calls). That engine still
sees no dynamic dispatch beyond annotation/constructor type inference
and nothing outside the linted corpus.

A rule is a ``Rule(name, description, check)`` where ``check`` maps a
``FileContext`` to ``RawFinding``s; the engine layers pragma and
baseline suppression on top.
"""

from __future__ import annotations

import ast
import re
from typing import Callable, List, NamedTuple


class RawFinding(NamedTuple):
    line: int
    col: int
    message: str


class FileContext(NamedTuple):
    path: str        # normalized posix path (repo-relative when possible)
    name: str        # basename, used for *_device.py scope decisions
    src: str
    tree: ast.Module


class Rule(NamedTuple):
    name: str
    description: str
    check: Callable[[FileContext], List[RawFinding]]


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - defensive
        return ""


def _is_device_file(name: str) -> bool:
    return name.endswith("_device.py")


def _is_regex_device_file(name: str) -> bool:
    return _is_device_file(name) and "regex" in name


_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)


def _functions(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, _FUNC_NODES):
            yield node


def _jit_decorated(fn) -> bool:
    """Matches @jax.jit, @_jax.jit, @jit, @partial(jax.jit, ...),
    @functools.partial(jax.jit, static_argnames=...)."""
    for dec in fn.decorator_list:
        txt = _unparse(dec)
        if "jax.jit" in txt or txt == "jit" or txt.startswith("jit("):
            return True
    return False


def _static_params(fn) -> set:
    """Parameter names pinned static via static_argnames/static_argnums:
    they are Python values inside the trace, not tracers."""
    names: set = set()
    pos = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for dec in fn.decorator_list:
        for node in ast.walk(dec):
            if not isinstance(node, ast.keyword) or node.arg not in (
                    "static_argnames", "static_argnums"):
                continue
            for c in ast.walk(node.value):
                if not isinstance(c, ast.Constant):
                    continue
                if isinstance(c.value, str):
                    names.add(c.value)
                elif isinstance(c.value, int) and 0 <= c.value < len(pos):
                    names.add(pos[c.value])
    return names


# ---------------------------------------------------------------------------
# rule 1: no-host-transfer-in-device-path
# ---------------------------------------------------------------------------

_HOST_TRANSFER_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get", "device_get",
}
_HOST_TRANSFER_METHODS = {"tolist", "item"}
_CONCRETIZERS = {"float", "int", "bool"}


def check_host_transfer(ctx: FileContext) -> List[RawFinding]:
    """Bug class: a silent device->host round trip inside a jit trace or
    a device engine — np.asarray / jax.device_get / .tolist() force a
    transfer (and a concretization error under jit), turning a fused
    device pipeline into a host sync. Scope: bodies of @jax.jit
    functions anywhere, and every function in ops/*_device.py
    (module-level code in device files is host-side compile-path setup
    and stays out of scope)."""
    out: List[RawFinding] = []
    seen: set = set()
    for fn in _functions(ctx.tree):
        if not (_is_device_file(ctx.name) or _jit_decorated(fn)):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            seen.add(id(node))
            ftxt = _unparse(node.func)
            if ftxt in _HOST_TRANSFER_CALLS:
                out.append(RawFinding(
                    node.lineno, node.col_offset,
                    f"host transfer `{ftxt}(...)` in a device path "
                    f"(jit scope or *_device.py); keep data on device "
                    f"(jnp.asarray) or hoist to the host-side caller"))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _HOST_TRANSFER_METHODS
                  and not node.args and not node.keywords):
                out.append(RawFinding(
                    node.lineno, node.col_offset,
                    f"`.{node.func.attr}()` forces a device->host "
                    f"transfer in a device path; hoist it out of the "
                    f"jit/device scope"))
            elif (isinstance(node.func, ast.Name)
                  and node.func.id in _CONCRETIZERS and node.args):
                atxt = _unparse(node.args[0])
                if "jnp." in atxt or "jax.lax" in atxt:
                    out.append(RawFinding(
                        node.lineno, node.col_offset,
                        f"`{node.func.id}(...)` on a traced expression "
                        f"concretizes (device->host sync) inside a "
                        f"device path"))
    return out


# ---------------------------------------------------------------------------
# rule 2: no-python-branch-on-traced
# ---------------------------------------------------------------------------

# attribute projections that are static Python values even on a tracer
_STATIC_ATTRS = {
    "shape", "dtype", "ndim", "size", "itemsize", "kind",
    "num_rows", "num_columns", "is_string", "storage_dtype",
}
_STATIC_CALLS = {"len", "isinstance", "hasattr", "getattr", "type"}
_HOST_NP_CALLS = {"jnp.iinfo", "jnp.finfo", "np.iinfo", "np.finfo",
                  "jnp.dtype", "np.dtype"}


def _is_traced(node: ast.AST, traced: set) -> bool:
    if isinstance(node, ast.Name):
        return node.id in traced
    if isinstance(node, ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return False
        return _is_traced(node.value, traced)
    if isinstance(node, ast.Subscript):
        return _is_traced(node.value, traced)
    if isinstance(node, ast.Call):
        ftxt = _unparse(node.func)
        if ftxt in _STATIC_CALLS or ftxt in _HOST_NP_CALLS:
            return False
        if ftxt.startswith(("jnp.", "jax.lax.", "lax.")):
            return True
        return (any(_is_traced(a, traced) for a in node.args)
                or any(_is_traced(k.value, traced)
                       for k in node.keywords))
    if isinstance(node, ast.BinOp):
        return (_is_traced(node.left, traced)
                or _is_traced(node.right, traced))
    if isinstance(node, ast.UnaryOp):
        return _is_traced(node.operand, traced)
    if isinstance(node, ast.BoolOp):
        return any(_is_traced(v, traced) for v in node.values)
    if isinstance(node, ast.Compare):
        return (_is_traced(node.left, traced)
                or any(_is_traced(c, traced) for c in node.comparators))
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return any(_is_traced(e, traced) for e in node.elts)
    if isinstance(node, ast.IfExp):
        return any(_is_traced(x, traced)
                   for x in (node.test, node.body, node.orelse))
    return False


def _walk_branches(stmts, traced: set, out: List[RawFinding]):
    for stmt in stmts:
        if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            value = stmt.value
            if value is not None and _is_traced(value, traced):
                targets = (stmt.targets
                           if isinstance(stmt, ast.Assign)
                           else [stmt.target])
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            traced.add(n.id)
        elif isinstance(stmt, (ast.If, ast.While)):
            if _is_traced(stmt.test, traced):
                kind = "if" if isinstance(stmt, ast.If) else "while"
                out.append(RawFinding(
                    stmt.lineno, stmt.col_offset,
                    f"Python `{kind}` on a traced value inside jit "
                    f"scope: the branch is resolved at trace time "
                    f"(or raises ConcretizationTypeError); use "
                    f"jnp.where / lax.cond"))
            _walk_branches(stmt.body, traced, out)
            _walk_branches(stmt.orelse, traced, out)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            _walk_branches(stmt.body, traced, out)
            _walk_branches(stmt.orelse, traced, out)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            _walk_branches(stmt.body, traced, out)
        elif isinstance(stmt, ast.Try):
            for block in (stmt.body, stmt.orelse, stmt.finalbody):
                _walk_branches(block, traced, out)
            for h in stmt.handlers:
                _walk_branches(h.body, traced, out)
        elif isinstance(stmt, _FUNC_NODES):
            # nested def (scan bodies, kernels): closes over the traced
            # environment, so inherit a copy plus its own parameters
            inner = set(traced)
            inner.update(a.arg for a in stmt.args.posonlyargs
                         + stmt.args.args + stmt.args.kwonlyargs)
            _walk_branches(stmt.body, inner, out)


def check_python_branch(ctx: FileContext) -> List[RawFinding]:
    """Bug class: `if cond:` on a traced array inside @jax.jit either
    burns the branch into the trace for whatever value the first call
    saw (silently wrong on later calls) or raises at trace time. Traced
    values are approximated as non-static parameters plus anything
    assigned from a jnp./lax. expression; .shape/.dtype/len() reads are
    static projections and stay branchable."""
    out: List[RawFinding] = []
    for fn in _functions(ctx.tree):
        if not _jit_decorated(fn):
            continue
        static = _static_params(fn)
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args
                  + fn.args.kwonlyargs]
        traced = {p for p in params if p not in static}
        _walk_branches(fn.body, traced, out)
    return out


# ---------------------------------------------------------------------------
# rule 3: sentinel-safety
# ---------------------------------------------------------------------------

def _is_sentinel_expr(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "max"
            and isinstance(node.value, ast.Call)
            and _unparse(node.value.func).split(".")[-1]
            in ("iinfo", "finfo"))


def check_sentinel_safety(ctx: FileContext) -> List[RawFinding]:
    """Bug class: dense_pk_join's sorted mode overwrites null keys with
    iinfo(dtype).max so the sort is globally monotone — which silently
    aliases a LEGITIMATE key equal to dtype max (ADVICE.md r5,
    planner.py:281). Using iinfo/finfo(...).max as a data sentinel is
    only safe next to a domain guard that excludes the sentinel value
    from the data; a function that uses the sentinel and has no
    `if ... <sentinel> ...: raise` (and no assert) is flagged."""
    out: List[RawFinding] = []
    for fn in _functions(ctx.tree):
        uses: list = []
        sentinel_names: set = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _any_sentinel(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        sentinel_names.add(t.id)
            if _is_sentinel_expr(node):
                uses.append(node)
        if not uses:
            continue

        def refs_sentinel(expr):
            for n in ast.walk(expr):
                if _is_sentinel_expr(n):
                    return True
                if isinstance(n, ast.Name) and n.id in sentinel_names:
                    return True
            return False

        guarded = False
        guard_tests: list = []
        for node in ast.walk(fn):
            if isinstance(node, ast.If) and refs_sentinel(node.test):
                if any(isinstance(x, ast.Raise)
                       for s in node.body + node.orelse
                       for x in ast.walk(s)):
                    guarded = True
                    guard_tests.append(node.test)
            elif isinstance(node, ast.Assert) and refs_sentinel(node.test):
                guarded = True
                guard_tests.append(node.test)
        if guarded:
            continue
        in_guard_test = {id(n) for t in guard_tests
                         for n in ast.walk(t)}
        for use in uses:
            if id(use) in in_guard_test:
                continue
            out.append(RawFinding(
                use.lineno, use.col_offset,
                "iinfo/finfo(...).max used as a data sentinel with no "
                "adjacent domain guard: a legitimate value equal to "
                "dtype max silently aliases the sentinel (the "
                "dense_pk_join bug class); raise when the declared "
                "domain touches dtype max, or pick an out-of-domain "
                "sentinel"))
    return out


def _any_sentinel(expr: ast.AST) -> bool:
    return any(_is_sentinel_expr(n) for n in ast.walk(expr))


# ---------------------------------------------------------------------------
# rule 4: padding-byte-invariant
# ---------------------------------------------------------------------------

def _contains_zero(node: ast.AST) -> bool:
    """Static over-approximation of `0 in <byteset expr>` for the
    constructions the regex engines actually use."""
    if isinstance(node, ast.Call):
        ftxt = _unparse(node.func)
        if ftxt == "range":
            a = node.args
            if len(a) == 1:
                return (isinstance(a[0], ast.Constant)
                        and isinstance(a[0].value, int)
                        and a[0].value >= 1)
            if len(a) >= 2:
                return (isinstance(a[0], ast.Constant)
                        and isinstance(a[0].value, int)
                        and a[0].value <= 0)
            return False
        if ftxt in ("set", "frozenset"):
            return bool(node.args) and _contains_zero(node.args[0])
        return False
    if isinstance(node, (ast.List, ast.Tuple, ast.Set)):
        return any(isinstance(e, ast.Constant) and e.value == 0
                   for e in node.elts)
    if isinstance(node, ast.Constant) and isinstance(node.value, bytes):
        return 0 in node.value
    return False


def check_padding_byte(ctx: FileContext) -> List[RawFinding]:
    """Bug class: the device regex engines pad every row's char matrix
    with 0x00 and rely on "no pattern byteset can match byte 0" so a
    match can never run past the end of a row into padding (ADVICE.md
    r5, regex_capture_device.py:207). Any byteset construction in a
    regex *_device.py that statically contains byte 0 breaks that
    invariant; deliberate sentinel machinery carries a pragma."""
    if not _is_regex_device_file(ctx.name):
        return []
    out: List[RawFinding] = []
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Call)
                and _unparse(node.func) in ("set", "frozenset")
                and node.args and _contains_zero(node.args[0])):
            out.append(RawFinding(
                node.lineno, node.col_offset,
                "byteset construction can contain byte 0, the row "
                "padding byte: a pattern atom matching NUL matches "
                "padding and crosses row boundaries; exclude 0 (start "
                "ranges at 1) or raise RegexUnsupported"))
    return out


# ---------------------------------------------------------------------------
# rule 5: dtype-width-discipline
# ---------------------------------------------------------------------------

_ARITH_OPS = (ast.Add, ast.Sub, ast.Mult, ast.FloorDiv, ast.Mod,
              ast.BitAnd, ast.BitOr, ast.BitXor, ast.LShift, ast.RShift)
_WIDTH_RE = {32: re.compile(r"\bu?int32\b"), 64: re.compile(r"\bu?int64\b")}


def _text_width(node: ast.AST):
    txt = _unparse(node)
    has32 = bool(_WIDTH_RE[32].search(txt))
    has64 = bool(_WIDTH_RE[64].search(txt))
    if has32 and not has64:
        return 32
    if has64 and not has32:
        return 64
    return None


def _scope_nodes(scope):
    """Walk a scope's statements without descending into nested defs
    (each function scope is processed on its own)."""
    body = scope.body if hasattr(scope, "body") else []
    stack = list(body)
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, _FUNC_NODES + (ast.ClassDef,)):
                stack.append(child)


def _name_widths(scope) -> dict:
    """name -> 32/64 for names whose every assignment in this scope
    pins one width (conflicting or unpinnable assignments drop the
    name)."""
    widths: dict = {}
    for node in _scope_nodes(scope):
        if not isinstance(node, ast.Assign):
            continue
        w = _text_width(node.value)
        for t in node.targets:
            if isinstance(t, ast.Name):
                if t.id in widths and widths[t.id] != w:
                    widths[t.id] = None
                else:
                    widths[t.id] = w
    return {k: v for k, v in widths.items() if v is not None}


def _width_of(node: ast.AST, widths: dict):
    if isinstance(node, ast.Name):
        return widths.get(node.id)
    return _text_width(node)


def check_dtype_width(ctx: FileContext) -> List[RawFinding]:
    """Bug class: int32/int64 mixing in ops/ arithmetic promotes (or,
    under strict dtypes, raises) at a point the author did not choose —
    index math built at int32 against an int64 gid wraps past 2^31 rows
    (the _dense_prologue range-check exists precisely because of this).
    Flags a binary arithmetic op whose operands are textually pinned to
    different widths; pick one width and cast at the boundary."""
    if "/ops/" not in ("/" + ctx.path):
        return []
    out: List[RawFinding] = []
    scopes = list(_functions(ctx.tree)) + [ctx.tree]
    for scope in scopes:
        widths = _name_widths(scope)
        for node in _scope_nodes(scope):
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, _ARITH_OPS)):
                continue
            lw = _width_of(node.left, widths)
            rw = _width_of(node.right, widths)
            if lw is not None and rw is not None and lw != rw:
                out.append(RawFinding(
                    node.lineno, node.col_offset,
                    f"implicit int{lw}/int{rw} mix in arithmetic: the "
                    f"promotion point is accidental and index math can "
                    f"wrap; cast both operands to one width "
                    f"explicitly"))
    return out


# ---------------------------------------------------------------------------
# rule 6: bitmask-via-helpers
# ---------------------------------------------------------------------------

_MASKY_NAME = re.compile(r"(^|_)(valid|validity|present|presence|mask)"
                         r"(_|$|\d)", re.IGNORECASE)


def _nonzero_compare(expr: ast.AST):
    for n in ast.walk(expr):
        if (isinstance(n, ast.Compare) and len(n.ops) == 1
                and isinstance(n.ops[0], ast.NotEq)):
            for side in (n.left, n.comparators[0]):
                if isinstance(side, ast.Constant) and side.value == 0:
                    return n
    return None


def check_bitmask_helpers(ctx: FileContext) -> List[RawFinding]:
    """Bug class: tpcds q3 derived group presence as `sums != 0`, so a
    group whose revenue sums to exactly zero (refunds) was dropped as
    absent (ADVICE.md r5, tpcds.py:807). A validity/presence mask must
    come from row counts (dense_id_counts(...) > 0) or the
    columnar/bitmask.py helpers — never from `aggregate != 0`, which
    conflates "no rows" with "rows summing to zero"."""
    out: List[RawFinding] = []
    for node in ast.walk(ctx.tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        names = [t.id for t in targets if isinstance(t, ast.Name)]
        if not any(_MASKY_NAME.search(n) for n in names):
            continue
        cmp_node = _nonzero_compare(value)
        if cmp_node is not None:
            out.append(RawFinding(
                cmp_node.lineno, cmp_node.col_offset,
                "validity/presence mask derived from `!= 0` on a "
                "value: zero-valued groups vanish (the tpcds_q3 bug "
                "class); derive presence from counts "
                "(dense_id_counts(...) > 0) or the columnar/bitmask "
                "helpers"))
    return out


# ---------------------------------------------------------------------------
# rule 7: fallback-must-be-recorded
# ---------------------------------------------------------------------------

def _calls_record_fallback(stmts) -> bool:
    for s in stmts:
        for n in ast.walk(s):
            if (isinstance(n, ast.Call)
                    and _unparse(n.func).endswith("record_fallback")):
                return True
    return False


def check_fallback_recorded(ctx: FileContext) -> List[RawFinding]:
    """Bug class: the regex/cast dispatchers silently handed whole columns
    to the host engine (ISSUE 2 motivation: round-5 could not say what ran
    on device), so a perf regression that was really a 100%-fallback went
    unexplained. In ops files (ops/*.py and any *_device.py), a device->host
    handoff must be accounted: an ``except ...Unsupported`` handler, or an
    explicit host-engine pin branch (``if <name> == "host":``), that does
    not call ``telemetry.record_fallback(...)`` is a finding. A handler
    whose body only re-raises is not a fallback and stays clean."""
    if not (_is_device_file(ctx.name) or "/ops/" in ("/" + ctx.path)):
        return []
    out: List[RawFinding] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ExceptHandler):
            names = []
            if node.type is not None:
                for n in ast.walk(node.type):
                    if isinstance(n, (ast.Name, ast.Attribute)):
                        names.append(_unparse(n).split(".")[-1])
            if not any(n.endswith("Unsupported") for n in names):
                continue
            if all(isinstance(s, ast.Raise) for s in node.body):
                continue  # pure re-raise: not a fallback
            if _calls_record_fallback(node.body):
                continue
            out.append(RawFinding(
                node.lineno, node.col_offset,
                "`except ...Unsupported` hands the column to the host "
                "engine without telemetry.record_fallback(...): the "
                "device/host split becomes invisible (the round-5 "
                "silent-fallback bug class); record with a reason, or "
                "re-raise"))
        elif isinstance(node, ast.If):
            test = node.test
            if not (isinstance(test, ast.Compare) and len(test.ops) == 1
                    and isinstance(test.ops[0], ast.Eq)
                    and isinstance(test.left, ast.Name)
                    and any(isinstance(c, ast.Constant) and c.value == "host"
                            for c in test.comparators)):
                continue
            if _calls_record_fallback(node.body):
                continue
            out.append(RawFinding(
                node.lineno, node.col_offset,
                "explicit host-engine branch (`== \"host\"`) without "
                "telemetry.record_fallback(...): a forced host pin is "
                "still a fallback the per-op accounting must see"))
    return out


# ---------------------------------------------------------------------------
# rule 8: jit-via-dispatch
# ---------------------------------------------------------------------------

def check_jit_via_dispatch(ctx: FileContext) -> List[RawFinding]:
    """Bug class: a batch-shaped op compiled with a direct ``@jax.jit``
    (or a bare ``jax.jit(...)`` call) re-traces and re-compiles for every
    distinct row count, bypassing the shape-bucketed executable cache in
    ``runtime/dispatch.py`` — exactly the per-shape compile storm the
    dispatch layer exists to absorb, and its padded-waste / hit-rate
    telemetry never sees the op. Scope: ops/*.py and any *_device.py
    (host-side drivers like chip_smoke.py measure whole pipelines and stay out
    of scope; runtime/dispatch.py itself owns the one legitimate jit).
    A deliberate jit — e.g. a wrapper whose shapes are
    block-quantized already — carries a
    ``# tpulint: disable=jit-via-dispatch`` pragma."""
    if not (_is_device_file(ctx.name) or "/ops/" in ("/" + ctx.path)):
        return []
    out: List[RawFinding] = []
    for fn in _functions(ctx.tree):
        if _jit_decorated(fn):
            # anchor on the decorator line so the pragma sits beside it
            dec_line = min((d.lineno for d in fn.decorator_list),
                           default=fn.lineno)
            out.append(RawFinding(
                dec_line, fn.col_offset,
                f"`{fn.name}` is compiled with a direct @jax.jit: each "
                f"distinct row count traces and compiles a fresh "
                f"executable; route the op through "
                f"runtime/dispatch.call/rowwise so row counts share "
                f"bucketed executables (pragma a deliberate jit)"))
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        ftxt = _unparse(node.func)
        if ftxt == "jax.jit" or ftxt.endswith(".jax.jit") or ftxt == "jit":
            out.append(RawFinding(
                node.lineno, node.col_offset,
                "bare `jax.jit(...)` in an ops file bypasses the "
                "shape-bucketed dispatch cache; use "
                "runtime/dispatch.call/rowwise (pragma a deliberate "
                "jit)"))
    return out


# ---------------------------------------------------------------------------
# rule 9: pipeline-stage-host-transfer
# ---------------------------------------------------------------------------

_PIPELINE_BLOCKING_CALLS = _HOST_TRANSFER_CALLS | {
    "jax.block_until_ready", "block_until_ready",
}


def _is_pipeline_file(name: str) -> bool:
    return "pipeline" in name


def check_pipeline_stage_host_transfer(ctx: FileContext) -> List[RawFinding]:
    """Bug class: a blocking device->host transfer inside a pipeline
    stage worker (np.asarray / jax.device_get on a device array,
    .tolist()/.item(), block_until_ready) parks a decode-pool thread on
    device completion — serializing exactly the IO/compute overlap the
    pipelined executor exists to create, invisibly (wall clock degrades
    to serial while every stage still "works"). Host-side bytes must
    come from the readers' host-staged decode (``stage="host"`` ->
    ``HostTableChunk``), never from re-fetching device arrays mid-stage.
    Scope: every function in a pipeline module (basename contains
    ``pipeline``); a reviewed-legitimate transfer carries a
    ``# tpulint: disable=pipeline-stage-host-transfer`` pragma stating
    why the stall is acceptable."""
    if not _is_pipeline_file(ctx.name):
        return []
    out: List[RawFinding] = []
    seen: set = set()
    for fn in _functions(ctx.tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            seen.add(id(node))
            ftxt = _unparse(node.func)
            if ftxt in _PIPELINE_BLOCKING_CALLS:
                out.append(RawFinding(
                    node.lineno, node.col_offset,
                    f"blocking `{ftxt}(...)` in a pipeline stage worker "
                    f"stalls the decode pool on device work and "
                    f"serializes the overlap; stage host bytes through "
                    f"the readers' host-staged decode (HostTableChunk) "
                    f"instead"))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _HOST_TRANSFER_METHODS
                  and not node.args and not node.keywords):
                out.append(RawFinding(
                    node.lineno, node.col_offset,
                    f"`.{node.func.attr}()` in a pipeline stage worker "
                    f"forces a device->host sync on a pool thread; keep "
                    f"stage payloads host-staged (HostTableChunk) until "
                    f"admission"))
    return out


# ---------------------------------------------------------------------------
# rule 10: fusion-region-host-sync
# ---------------------------------------------------------------------------

_FUSION_BLOCKING_CALLS = _PIPELINE_BLOCKING_CALLS


def _is_fusion_file(name: str) -> bool:
    return "fusion" in name


def check_fusion_region_host_sync(ctx: FileContext) -> List[RawFinding]:
    """Bug class: the whole point of runtime/fusion.py is that a fusible
    region lowers to ONE traced executable — every node callable runs
    inside a single dispatch.call trace. A host materialization inside
    one of those callables (np.asarray / jax.device_get on a traced
    table, .tolist()/.item(), block_until_ready) either raises a
    ConcretizationTypeError the first time the region actually fuses,
    or — worse — works on the staged path and under dispatch's inline
    fallback, so the sync ships silently and splits the region back
    into per-op round trips the moment someone measures the staged
    path. Scope: every function in a fusion module (basename contains
    ``fusion``); host-side plan construction that legitimately reads
    binding row counts does so via .num_rows / .shape, which are static
    and stay clean. A reviewed-legitimate transfer carries a
    ``# tpulint: disable=fusion-region-host-sync`` pragma stating why
    the region must break there."""
    if not _is_fusion_file(ctx.name):
        return []
    out: List[RawFinding] = []
    seen: set = set()
    for fn in _functions(ctx.tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in seen:
                continue
            seen.add(id(node))
            ftxt = _unparse(node.func)
            if ftxt in _FUSION_BLOCKING_CALLS:
                out.append(RawFinding(
                    node.lineno, node.col_offset,
                    f"host sync `{ftxt}(...)` in a fusion module: inside "
                    f"a fused-region callable it concretizes mid-trace "
                    f"and splits the single-executable region; resolve "
                    f"host values from binding metadata (.num_rows / "
                    f".shape) at plan-build time instead"))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _HOST_TRANSFER_METHODS
                  and not node.args and not node.keywords):
                out.append(RawFinding(
                    node.lineno, node.col_offset,
                    f"`.{node.func.attr}()` in a fusion module forces a "
                    f"device->host sync; a fused-region callable must "
                    f"stay traceable end to end — hoist the read to the "
                    f"region boundary (execute()'s meta outputs)"))
    return out


# ---------------------------------------------------------------------------
# rule 11: error-must-classify
# ---------------------------------------------------------------------------

# A swallow is acceptable when the handler visibly accounts for the error:
# re-raising (through the resilience taxonomy or otherwise), recording it
# (telemetry events / counters / logs), or routing it into the shared
# retry/degradation policy.
_CLASSIFY_CALL_SUFFIXES = (
    "record_fallback", "record_resilience", "record_spill",
    "record_compile_cache", "classify", "retrying", "escalate",
    "retry_or_none",
)
_CLASSIFY_ATTR_CALLS = {"inc", "warning", "error", "exception"}


def _is_resilient_scope_file(ctx: FileContext) -> bool:
    path = str(ctx.path).replace("\\", "/")
    return ("resilience" in ctx.name or "faults" in ctx.name
            or "/runtime/" in path or "/parallel/" in path
            or _is_device_file(ctx.name))


def _handler_accounts(stmts) -> bool:
    for s in stmts:
        for n in ast.walk(s):
            if isinstance(n, ast.Raise):
                return True
            if isinstance(n, ast.Call):
                ftxt = _unparse(n.func)
                if ftxt.endswith(_CLASSIFY_CALL_SUFFIXES):
                    return True
                if (isinstance(n.func, ast.Attribute)
                        and n.func.attr in _CLASSIFY_ATTR_CALLS):
                    return True
    return False


def check_error_must_classify(ctx: FileContext) -> List[RawFinding]:
    """Bug class: a bare ``except Exception`` (or ``except:``) on the
    device path that swallows the error silently — no re-raise, no
    telemetry, no route into the resilience policy — converts every
    failure mode (device OOM, transport loss, genuine bugs) into silent
    wrong-or-missing results, exactly what the structured taxonomy in
    ``runtime/resilience.py`` exists to prevent. Every seam must either
    re-raise (letting ``classify``/``retrying`` own the decision) or
    visibly account for the swallow (record_* event, counter ``.inc()``,
    log). Scope: resilience/faults modules, ``runtime/``/``parallel/``
    packages, and device-op files — NOT bench/tools code, whose
    best-effort try/except-pass posture is deliberate. ``except
    BaseException`` unwind paths are exempt (they exist to release
    resources and re-raise or return deliberately). A reviewed-legitimate
    swallow carries a ``# tpulint: disable=error-must-classify`` pragma
    stating why."""
    if not _is_resilient_scope_file(ctx):
        return []
    out: List[RawFinding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        # only the broad catches: bare `except:` and `except Exception`
        # (BaseException handlers are deliberate unwind paths)
        if node.type is not None and _unparse(node.type) != "Exception":
            continue
        if _handler_accounts(node.body):
            continue
        out.append(RawFinding(
            node.lineno, node.col_offset,
            "broad `except Exception` on the device path swallows the "
            "error unclassified: re-raise through the resilience "
            "taxonomy (runtime/resilience.classify / retrying), or "
            "account for the swallow with a telemetry record_* event, "
            "counter .inc(), or log"))
    return out


# ---------------------------------------------------------------------------
# rule 12: serving-path telemetry must carry session attribution
# ---------------------------------------------------------------------------

# the telemetry emitters whose events a multi-session operator reads
_SESSION_RECORD_NAMES = {
    "record_server", "record_fallback", "record_spill",
    "record_resilience", "record_dispatch", "record_compile_cache",
}


def _is_server_file(name: str) -> bool:
    return "server" in name


def _session_scope_spans(tree: ast.Module) -> List[tuple]:
    """(first, last) line ranges of ``with session_scope(...)`` blocks —
    every event emitted inside one is stamped by the scope itself."""
    spans: List[tuple] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            if "session_scope" in _unparse(item.context_expr):
                spans.append((node.lineno, node.end_lineno or node.lineno))
                break
    return spans


def check_server_session_id(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-7 bug class: the serving runtime multiplexes N sessions over
    one process, so an un-attributed telemetry event (a fallback, a
    spill, a served/rejected record) is unactionable — the operator
    cannot tell WHOSE query fell back. In server-scope files every
    telemetry ``record_*`` call must carry a ``session=`` keyword, splat
    one through ``**kwargs``, or run inside ``with session_scope(sid):``
    (which stamps every event emitted under it)."""
    if not _is_server_file(ctx.name):
        return []
    spans = _session_scope_spans(ctx.tree)
    out: List[RawFinding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = _unparse(node.func).rsplit(".", 1)[-1]
        if fn not in _SESSION_RECORD_NAMES:
            continue
        if any(kw.arg == "session" or kw.arg is None
               for kw in node.keywords):
            continue  # explicit kwarg, or a **splat that may carry it
        if any(lo <= node.lineno <= hi for lo, hi in spans):
            continue  # session_scope stamps the event
        out.append(RawFinding(
            node.lineno, node.col_offset,
            f"serving-path telemetry `{fn}(...)` has no session "
            "attribution: pass session=<sid>, or emit inside "
            "`with session_scope(sid):` so the scope stamps it"))
    return out


# ---------------------------------------------------------------------------
# rule 13: reservation-release-in-finally
# ---------------------------------------------------------------------------

_RESERVE_METHODS = {"reserve", "reserve_blocking"}


def _is_reservation_scope_file(ctx: FileContext) -> bool:
    path = "/" + str(ctx.path).replace("\\", "/")
    return ("memory" in ctx.name or "server" in ctx.name
            or "degrade" in ctx.name or "outofcore" in ctx.name
            or "/runtime/" in path or "/parallel/" in path)


def _top_functions(tree: ast.Module):
    """Outermost function scopes only: a nested worker shares its
    parent's unwind structure (the parent's finally releases what the
    worker reserved), so the grant/release pairing is judged per
    top-level function with every nested def folded in."""
    out: list = []

    def visit(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FUNC_NODES):
                out.append(child)
            else:
                visit(child)

    visit(tree)
    return out


def check_reservation_release(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-8 bug class: a ``limiter.reserve(...)`` /
    ``reserve_blocking(...)`` grant released only on the success path
    leaks its bytes the first time the guarded work raises — the limiter
    never drains, admission wedges at the high watermark, and every later
    query parks forever (the exact failure the degradation ladder cannot
    recover from, because the leaked usage is phantom). A function that
    both reserves and releases on the same limiter object must put at
    least one release in an exception-safe position: a ``finally`` block,
    or an except handler that re-raises (the unwind-then-transfer idiom —
    on success the caller owns the grant). A reserve with NO matching
    release is ownership transfer and stays clean; ``.release()`` on
    other objects (locks, semaphores) never pairs with a reserve and is
    ignored. Scope: memory/server/degrade/outofcore basenames and the
    ``runtime/``/``parallel/`` packages."""
    if not _is_reservation_scope_file(ctx):
        return []
    out: List[RawFinding] = []
    for fn in _top_functions(ctx.tree):
        reserves: dict = {}
        releases: dict = {}
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            base = _unparse(node.func.value)
            if node.func.attr in _RESERVE_METHODS:
                reserves.setdefault(base, []).append(node)
            elif node.func.attr == "release":
                releases.setdefault(base, []).append(node)
        if not reserves:
            continue
        # calls sitting in an exception-safe position: a finally block,
        # or an except handler that re-raises (unwind path)
        safe: set = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Try):
                for s in node.finalbody:
                    for n in ast.walk(s):
                        safe.add(id(n))
            elif isinstance(node, ast.ExceptHandler):
                if any(isinstance(x, ast.Raise)
                       for s in node.body for x in ast.walk(s)):
                    for s in node.body:
                        for n in ast.walk(s):
                            safe.add(id(n))
        for base, res_calls in reserves.items():
            rels = releases.get(base, [])
            if not rels:
                continue  # ownership transfer: the consumer releases
            if any(id(r) in safe for r in rels):
                continue
            for rc in res_calls:
                out.append(RawFinding(
                    rc.lineno, rc.col_offset,
                    f"`{base}.{rc.func.attr}(...)` is released only on "
                    f"the success path: an exception between grant and "
                    f"release leaks the bytes and wedges admission at "
                    f"the watermark; release in a `finally` (or an "
                    f"except handler that re-raises, transferring "
                    f"ownership on success)"))
    return out


def check_span_scope(ctx: FileContext) -> List[RawFinding]:
    """Span lifecycle discipline: ``spans.span(...)`` / ``spans.child(...)``
    acquired OUTSIDE a ``with`` statement (or a decorator expression) is a
    leak waiting to happen — an un-exited span never stamps its end time,
    never emits, pins its subtree open in the flight recorder, and leaves
    the thread-local stack pointing at a dead frame so every LATER span in
    that thread parents wrong. The factories are context managers by
    contract: the only sound acquisition is ``with spans.span(...)`` /
    ``with spans.child(...) as s`` (or inside a decorator). Assigning the
    result, returning it, or passing it along is flagged. The spans module
    itself (the factories' home) is exempt."""
    if ctx.name == "spans.py":
        return []
    # module aliases for telemetry.spans and bare-imported factory names
    mod_aliases = set()
    fn_aliases = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module.endswith("telemetry") or node.module == "telemetry":
                for a in node.names:
                    if a.name == "spans":
                        mod_aliases.add(a.asname or a.name)
            elif node.module.endswith("telemetry.spans"):
                for a in node.names:
                    if a.name in ("span", "child"):
                        fn_aliases.add(a.asname or a.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.endswith("telemetry.spans"):
                    mod_aliases.add(a.asname or a.name)
    if not mod_aliases and not fn_aliases:
        return []
    # calls sitting where a context manager belongs: with-items and
    # decorators (the two scoped acquisition forms)
    scoped: set = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                scoped.add(id(item.context_expr))
        elif isinstance(node, _FUNC_NODES):
            for dec in node.decorator_list:
                for n in ast.walk(dec):
                    scoped.add(id(n))
    out: List[RawFinding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or id(node) in scoped:
            continue
        func = node.func
        hit = None
        if isinstance(func, ast.Attribute) and func.attr in ("span", "child"):
            base = _unparse(func.value)
            if (base in mod_aliases or base.endswith(".spans")
                    or base.endswith("telemetry.spans")):
                hit = f"{base}.{func.attr}"
        elif isinstance(func, ast.Name) and func.id in fn_aliases:
            hit = func.id
        if hit is None:
            continue
        out.append(RawFinding(
            node.lineno, node.col_offset,
            f"`{hit}(...)` acquired outside a `with` statement: an "
            f"un-exited span never records, wedges the flight-recorder "
            f"tree open, and corrupts the thread-local span stack for "
            f"every later span on this thread; acquire it as "
            f"`with {hit}(...) as s:` (or in a decorator)"))
    return out


# ---------------------------------------------------------------------------
# rule 15: payload-must-verify
# ---------------------------------------------------------------------------


def check_payload_verify(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-10 bug class: a managed payload (spill file, checkpoint
    partial, wire frame) read back with a raw binary ``fh.read()``
    bypasses the integrity trailer — a torn write or bit-flip decodes
    into garbage columns instead of raising a classified
    ``CorruptDataError`` at the seam. Any top-level function in the
    reservation-scope files (memory/server/degrade/outofcore basenames,
    ``runtime/``/``parallel/`` packages) that opens a file in binary
    read mode and calls ``.read()`` on the handle must also touch the
    verify seam: a ``verify``-named callable/reference or an
    ``integrity.read_payload_file``-style helper. The integrity module
    itself (the seam's home, where the raw read IS the implementation)
    is exempt."""
    if not _is_reservation_scope_file(ctx) or "integrity" in ctx.name:
        return []
    out: List[RawFinding] = []
    for fn in _top_functions(ctx.tree):
        # a function touching the verify seam anywhere is trusted:
        # the checked read path and the raw read may share one scope
        # (e.g. a length probe before the verified payload read)
        verified = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and (
                    "verify" in node.attr
                    or node.attr.startswith("read_payload")):
                verified = True
                break
            if isinstance(node, ast.Name) and "verify" in node.id:
                verified = True
                break
        if verified:
            continue
        # handles bound from binary-read open(): `with open(..) as fh`
        # or `fh = open(..)`
        def _is_binary_read_open(call) -> bool:
            if not (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Name)
                    and call.func.id == "open"):
                return False
            mode = None
            if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
                mode = call.args[1].value
            for kw in call.keywords:
                if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                    mode = kw.value.value
            return isinstance(mode, str) and "b" in mode and "r" in mode

        handles: set = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if (_is_binary_read_open(item.context_expr)
                            and isinstance(item.optional_vars, ast.Name)):
                        handles.add(item.optional_vars.id)
            elif isinstance(node, ast.Assign):
                if _is_binary_read_open(node.value):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            handles.add(tgt.id)
        if not handles:
            continue
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "read"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in handles):
                out.append(RawFinding(
                    node.lineno, node.col_offset,
                    f"raw `{node.func.value.id}.read()` of a managed "
                    f"payload bypasses the integrity trailer: a torn "
                    f"write or bit-flip decodes into garbage instead of "
                    f"raising a classified CorruptDataError; read it "
                    f"through `integrity.read_payload_file(...)` (or "
                    f"verify the blob with `integrity.verify(...)`)"))
    return out


def check_cache_key_fingerprint(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-11 bug class: a result-cache ``get``/``put`` keyed by the
    plan signature ALONE serves yesterday's bytes the moment the bound
    data changes — the key's second half (the input-content fingerprint)
    is what invalidates on data change, and ``runtime/resultcache.py``
    rejects fingerprint-less keys at runtime. This is the static half:
    in cache-scope files (a ``cache`` basename, or the reservation-scope
    runtime/parallel set), any ``.get(...)``/``.put(...)`` on a
    cache-named receiver whose key argument is visibly signature-only —
    a bare ``*sig*``-named reference, a direct ``plan_signature(...)``
    call, or a ``CacheKey`` constructed without (or with an empty)
    fingerprint — is flagged. Keys built through ``cache_key(...)`` or
    carrying a fingerprint are clean; no cross-module dataflow, so a
    laundered signature-only key still needs the runtime check."""
    if not (_is_reservation_scope_file(ctx) or "cache" in ctx.name):
        return []
    out: List[RawFinding] = []

    def _ident(node) -> str:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            return node.attr
        return ""

    def _sig_only_name(name: str) -> bool:
        low = name.lower()
        return ("sig" in low and "fingerprint" not in low
                and "fp" not in low and "key" not in low)

    def _suspect_key(key) -> "str | None":
        if isinstance(key, ast.Call):
            callee = _ident(key.func)
            if callee == "plan_signature":
                return ("a raw `plan_signature(...)` digest is the "
                        "signature half only")
            if callee == "CacheKey":
                fp = None
                if len(key.args) >= 2:
                    fp = key.args[1]
                for kw in key.keywords:
                    if kw.arg == "fingerprint":
                        fp = kw.value
                if fp is None:
                    return "CacheKey constructed without a fingerprint"
                if (isinstance(fp, ast.Constant)
                        and isinstance(fp.value, str)
                        and not fp.value.strip()):
                    return "CacheKey fingerprint is an empty string"
            return None
        name = _ident(key)
        if name and _sig_only_name(name):
            return f"key `{name}` names only the plan signature"
        return None

    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("get", "put")
                and node.args):
            continue
        recv = _unparse(node.func.value).lower()
        if "cache" not in recv.rsplit(".", 1)[-1]:
            continue
        why = _suspect_key(node.args[0])
        if why is None:
            continue
        out.append(RawFinding(
            node.lineno, node.col_offset,
            f"result-cache .{node.func.attr}(...) keyed without the "
            f"input fingerprint ({why}): a signature-only key serves "
            f"stale results across data changes; derive the key with "
            f"`resultcache.cache_key(plan, bindings)` (or pass a "
            f"`source_fingerprint`) so content invalidates it"))
    return out


# ---------------------------------------------------------------------------
# rule 17: compress-inside-seal
# ---------------------------------------------------------------------------

_DECODE_CALL_NAMES = {"decode_array", "unpack_array"}
_VERIFY_CALL_HINT = "verify"


def _module_references_compress(tree: ast.Module) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id == "compress":
            return True
        if isinstance(node, ast.Attribute) and node.attr == "compress":
            return True
        if isinstance(node, ast.ImportFrom):
            if node.module and node.module.endswith("compress"):
                return True
            if any((a.asname or a.name) == "compress" for a in node.names):
                return True
    return False


def check_compress_inside_seal(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-12 bug class: the ordering contract is **compress -> seal**
    on write and **verify -> decompress** on read — the integrity
    trailer must be the OUTERMOST wrapper so the crc covers the stored
    (compressed) bytes and no decode work is spent on bytes that fail
    verification. Two static halves:

    1. A reservation-scope module (memory/server/degrade/outofcore
       basenames, ``runtime/``/``parallel/`` packages) that seals
       payloads (``integrity.seal(...)`` / ``write_payload_file(...)``)
       without referencing the ``runtime/compress.py`` codec anywhere is
       bypassing the compression seam: its at-rest bytes are sealed raw
       and the per-seam ``compress.*`` toggles silently do nothing
       there. Module granularity keeps pre-compressed pass-through
       clean (e.g. dcn's send path seals a blob its serializer already
       compressed — the module references the codec, so it is trusted).
    2. A function that decompresses a payload (``decode_array`` /
       ``unpack_array`` / a ``*decompress*``-named callee) at an
       earlier line than its own verify call (``*verify*`` /
       ``read_payload_file``-style) is decoding unverified bytes —
       exactly the wasted-work/garbage-decode order the contract bans.

    The codec, integrity and fault-injection modules (the seams' homes)
    are exempt."""
    if not _is_reservation_scope_file(ctx):
        return []
    # exact basenames: the seams' homes, where the raw seal/decode IS
    # the implementation (substring matching would also exempt the
    # seeded fixture, whose name legitimately contains "compress")
    if ctx.name in ("integrity.py", "compress.py", "faults.py"):
        return []
    out: List[RawFinding] = []
    # half 1: seal without a codec reference anywhere in the module
    if not _module_references_compress(ctx.tree):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = None
            if isinstance(node.func, ast.Attribute):
                if node.func.attr in ("seal", "write_payload_file"):
                    name = node.func.attr
            elif isinstance(node.func, ast.Name):
                if node.func.id in ("seal", "write_payload_file"):
                    name = node.func.id
            if name is None:
                continue
            out.append(RawFinding(
                node.lineno, node.col_offset,
                f"`{name}(...)` seals a payload in a module that never "
                f"references the runtime/compress codec: the compress "
                f"seam is bypassed, at-rest bytes stay raw, and the "
                f"per-seam compress.* toggles silently do nothing here; "
                f"route the payload through compress.pack_array/"
                f"encode_array (or its seam gate) BEFORE sealing"))
    # half 2: decompress at an earlier line than the same function's
    # verify — decoding bytes nothing has verified yet
    for fn in _top_functions(ctx.tree):
        decode_line = None
        verify_line = None
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = (node.func.attr if isinstance(node.func, ast.Attribute)
                      else node.func.id if isinstance(node.func, ast.Name)
                      else "")
            if callee in _DECODE_CALL_NAMES or "decompress" in callee:
                if decode_line is None or node.lineno < decode_line:
                    decode_line = node.lineno
            elif (_VERIFY_CALL_HINT in callee
                  or callee.startswith("read_payload")):
                if verify_line is None or node.lineno < verify_line:
                    verify_line = node.lineno
        if (decode_line is not None and verify_line is not None
                and decode_line < verify_line):
            out.append(RawFinding(
                decode_line, 0,
                f"decompress at line {decode_line} runs before this "
                f"function's verify at line {verify_line}: the read "
                f"contract is verify -> decompress -> post-decode check "
                f"(the trailer covers the compressed bytes; decoding "
                f"first spends work on — and can crash on — bytes "
                f"verification would have rejected)"))
    return out


# ---------------------------------------------------------------------------
# rule 18: worker-exit-must-classify
# ---------------------------------------------------------------------------

# receivers whose .wait()/.poll() plausibly return a subprocess exit
# status (filters out the ubiquitous Event/Condition/Lock .wait())
_PROC_RECEIVER_HINTS = ("proc", "popen", "process", "child", "worker")


def _is_fleet_scope_file(ctx: FileContext) -> bool:
    return _is_reservation_scope_file(ctx) or "fleet" in ctx.name


def _proc_exit_reads(fn) -> List[ast.AST]:
    """AST sites inside ``fn`` that CONSUME a subprocess exit status:
    ``.returncode`` reads, ``proc.wait()``/``proc.poll()`` whose value is
    used (a bare-expression ``proc.wait(...)`` merely synchronizes and is
    exempt), and ``os.waitpid(...)``."""
    discarded = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Expr) and isinstance(node.value, ast.Call):
            discarded.add(id(node.value))
    out: List[ast.AST] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute) and node.attr == "returncode":
            out.append(node)
        elif isinstance(node, ast.Call) and id(node) not in discarded:
            if isinstance(node.func, ast.Attribute):
                if node.func.attr in ("wait", "poll"):
                    recv = _unparse(node.func.value).lower()
                    last = recv.rsplit(".", 1)[-1]
                    if any(h in last for h in _PROC_RECEIVER_HINTS):
                        out.append(node)
                elif node.func.attr == "waitpid":
                    out.append(node)
            elif (isinstance(node.func, ast.Name)
                    and node.func.id == "waitpid"):
                out.append(node)
    return out


def _fn_classifies_or_accounts(fn) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Raise):
            return True
        if not isinstance(node, ast.Call):
            continue
        ftxt = _unparse(node.func)
        if "classify" in ftxt:
            return True
        if ftxt.endswith(_CLASSIFY_CALL_SUFFIXES + ("record_fleet",)):
            return True
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _CLASSIFY_ATTR_CALLS):
            return True
    return False


def check_worker_exit_classified(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-14 bug class: supervision code that reads a worker
    subprocess's exit status — ``proc.returncode``, a consumed
    ``proc.wait()``/``proc.poll()``, ``os.waitpid`` — and acts on the
    raw integer. A nonzero exit, a signal death (negative returncode)
    and an unresponsive worker are DIFFERENT failure shapes with
    different recovery policy (failover vs restart vs quarantine), and
    the resilience taxonomy is where that mapping lives
    (``resilience.classify_worker_exit`` builds the classified
    ``ReplicaDeadError`` with cause/replica context embedded). A
    function that consumes an exit status must route through a
    ``classify*`` call, raise, or visibly account for the read
    (``record_*`` event, counter ``.inc()``, log) — a silently absorbed
    exit code turns replica death into an unexplained hang. A
    bare-expression ``proc.wait(...)`` used purely as a join barrier is
    exempt (the status is not consumed). Scope: supervision homes —
    fleet-named files plus the reservation scope."""
    if not _is_fleet_scope_file(ctx):
        return []
    out: List[RawFinding] = []
    for fn in _top_functions(ctx.tree):
        reads = _proc_exit_reads(fn)
        if not reads or _fn_classifies_or_accounts(fn):
            continue
        for node in reads:
            out.append(RawFinding(
                node.lineno, node.col_offset,
                f"`{_unparse(node)}` consumes a worker exit status but "
                f"nothing in `{fn.name}` classifies or accounts for it: "
                f"route the shape through resilience.classify_worker_exit "
                f"(nonzero exit / signal death / unresponsive map to a "
                f"classified ReplicaDeadError), raise, or make the read "
                f"visible (record_* event, counter .inc(), log)"))
    return out


# ---------------------------------------------------------------------------
# rule 23: placement-must-record
# ---------------------------------------------------------------------------


def _is_placement_scope_file(ctx: FileContext) -> bool:
    """Routing/supervision homes: fleet- and cluster-named files (the
    deliberately narrow scope — generic selection helpers elsewhere in
    runtime/ are not placement decisions)."""
    return "fleet" in ctx.name or "cluster" in ctx.name


_PLACEMENT_NAME_TOKENS = ("pick", "route", "choose", "place", "owner",
                          "rehome")
_SELECTION_CALLS = {"min", "max", "sorted", "choice", "choices", "randint",
                    "randrange", "sample", "shuffle"}


def _placement_selections(fn) -> List[ast.AST]:
    out: List[ast.AST] = []
    for node in ast.walk(fn):
        if (isinstance(node, ast.Call)
                and _unparse(node.func).split(".")[-1] in _SELECTION_CALLS):
            out.append(node)
    return out


def check_placement_recorded(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-17 bug class (rule 23): an invisible routing decision. The mesh's
    whole failure story is replayed from telemetry — which host a query
    landed on, whether locality held or a shard re-homed, why a fan-out
    fanned where it did. A fleet/cluster function that IS a placement
    site (its name says so: pick/route/choose/place/owner/rehome) and
    actually selects among candidates (``min``/``max``/``sorted``/
    ``random.*``) but emits nothing — no ``record_*`` event, no counter
    ``.inc()``, no raise, no log — makes the routing table
    unreconstructable exactly when a failover goes wrong. Placement
    decisions must be recorded at the decision site. Scope: fleet- and
    cluster-named files; functions whose selection is pure arithmetic
    (no selection call) are exempt."""
    if not _is_placement_scope_file(ctx):
        return []
    out: List[RawFinding] = []
    for fn in _top_functions(ctx.tree):
        lname = fn.name.lower()
        if not any(tok in lname for tok in _PLACEMENT_NAME_TOKENS):
            continue
        selections = _placement_selections(fn)
        if not selections or _fn_classifies_or_accounts(fn):
            continue
        for node in selections:
            out.append(RawFinding(
                node.lineno, node.col_offset,
                f"`{_unparse(node)[:60]}` selects a placement in "
                f"`{fn.name}` but nothing records the decision: emit a "
                f"record_* telemetry event or bump a counter (.inc()) at "
                f"the decision site — an unrecorded routing choice makes "
                f"cross-host failover unreconstructable from telemetry"))
    return out


# ---------------------------------------------------------------------------
# rule 24: rtfilter-decision-must-record
# ---------------------------------------------------------------------------


def _is_rtfilter_scope_file(ctx: FileContext) -> bool:
    """Runtime-filter planner homes: rtfilter-named files only (the
    deliberately narrow scope — fusion.py's injection pass delegates
    every on/off/sizing choice to ``rtfilter.decide``, which is where
    this rule holds)."""
    return "rtfilter" in ctx.name


_RTFILTER_DECISION_TOKENS = ("decide", "gate", "size", "choose", "should")


def _rtfilter_decision_sites(fn) -> List[ast.AST]:
    """The choices that must be visible: a threshold comparison (the
    on/off gate) or a call into the sizing seam (``optimal_params``)."""
    out: List[ast.AST] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Compare):
            out.append(node)
        elif (isinstance(node, ast.Call)
                and _unparse(node.func).split(".")[-1] == "optimal_params"):
            out.append(node)
    return out


def _fn_records_rtfilter(fn) -> bool:
    for node in ast.walk(fn):
        if (isinstance(node, ast.Call)
                and _unparse(node.func).endswith("record_rtfilter")):
            return True
    return False


def check_rtfilter_decision_recorded(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-18 bug class (rule 24): an invisible runtime-filter
    decision. The bloom pushdown is adaptive — a learned selectivity EMA
    gates it on/off and sizes the filter — so when a query slows down
    (filter applied to a non-selective join) or fails to speed up
    (filter gated off on stale history), the ONLY way to reconstruct
    what the planner chose and why is the decision record. A
    decision-named function in an rtfilter file (decide/gate/size/
    choose/should) that actually makes a choice — a threshold
    comparison or a sizing call (``optimal_params``) — but emits
    nothing (no ``record_rtfilter``/``record_*`` event, no counter
    ``.inc()``, no raise) turns every gating bug into an unexplained
    plan change. Every decision carries a mandatory reason
    (``telemetry.record_rtfilter`` enforces non-empty). Functions with
    no comparison or sizing call are exempt (pure arithmetic is not a
    decision)."""
    if not _is_rtfilter_scope_file(ctx):
        return []
    out: List[RawFinding] = []
    for fn in _top_functions(ctx.tree):
        lname = fn.name.lower()
        if not any(tok in lname for tok in _RTFILTER_DECISION_TOKENS):
            continue
        sites = _rtfilter_decision_sites(fn)
        if (not sites or _fn_records_rtfilter(fn)
                or _fn_classifies_or_accounts(fn)):
            continue
        for node in sites:
            out.append(RawFinding(
                node.lineno, node.col_offset,
                f"`{_unparse(node)[:60]}` decides a runtime-filter "
                f"on/off/sizing in `{fn.name}` but nothing records the "
                f"decision: emit record_rtfilter(...) with a reason (or "
                f"a counter .inc() / raise) at the decision site — an "
                f"unrecorded gating choice makes adaptive plan changes "
                f"unexplainable from telemetry"))
    return out


# ---------------------------------------------------------------------------
# rule 25: exchange-overflow-must-classify
# ---------------------------------------------------------------------------


def _is_exchange_scope_file(ctx: FileContext) -> bool:
    """Exchange homes: the hash-partitioned repartition paths
    (runtime/exchange.py, parallel/shuffle.py) where a capacity overflow
    is a recoverable, classifiable event — never a silent drop."""
    return "exchange" in ctx.name or "shuffle" in ctx.name


def _overflow_branch_sites(fn) -> List[ast.AST]:
    """Host-side sites that CONSUME an overflow flag: ``if``/``while``
    tests and conditional expressions naming an overflow value. A device
    function merely RETURNING the flag to its jit boundary is exempt —
    that is how the flag reaches the host in the first place."""
    out: List[ast.AST] = []
    for node in ast.walk(fn):
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            if "overflow" in _unparse(node.test).lower():
                out.append(node.test)
    return out


def _fn_classifies_overflow(fn) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Raise):
            return True
        if not isinstance(node, ast.Call):
            continue
        last = _unparse(node.func).split(".")[-1]
        if "classify" in last or last == "escalate":
            return True
    return False


def check_exchange_overflow_classified(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-19 bug class (rule 25): a bare-boolean overflow path in an
    exchange/shuffle file. The distributed exchange's whole overflow
    contract is the spill-aware ladder — an overflowing pack escalates
    through ``resilience.escalate``, demotes to chunked flights, and
    anything that escapes is a classified ``CapacityOverflow``
    (``shuffle.classify_overflow`` with partition/capacity context). A
    function that branches on an overflow flag but neither classifies
    (``classify*`` call), escalates (``resilience.escalate``), nor
    raises has reinvented the pre-ladder one-shot retry: rows get
    silently dropped or capacities silently capped, and the failure
    surfaces three layers up as wrong answers instead of a
    CapacityOverflow naming the hot partition. Device functions that
    only COMPUTE and return the flag are exempt (the host consumer owns
    the classification). Scope: exchange-/shuffle-named files."""
    if not _is_exchange_scope_file(ctx):
        return []
    out: List[RawFinding] = []
    for fn in _top_functions(ctx.tree):
        sites = _overflow_branch_sites(fn)
        if not sites or _fn_classifies_overflow(fn):
            continue
        for node in sites:
            out.append(RawFinding(
                node.lineno, node.col_offset,
                f"`{_unparse(node)[:60]}` branches on an overflow flag "
                f"in `{fn.name}` but nothing classifies it: route the "
                f"overflow through shuffle.classify_overflow / "
                f"resilience.escalate (-> CapacityOverflow with "
                f"partition/capacity context) or raise — a bare-boolean "
                f"overflow path silently drops rows and surfaces as "
                f"wrong answers instead of a classified error"))
    return out


# ---------------------------------------------------------------------------
# rule 26: peer-flight-must-verify-manifest
# ---------------------------------------------------------------------------


def _is_peer_flight_scope_file(ctx: FileContext) -> bool:
    """Direct-flight homes: the exchange/cluster/dcn/shuffle layers
    where one host receives flight bytes ANOTHER host produced and the
    supervisor's manifest fingerprint is the only identity check
    (flight-named files are the same surface under another name)."""
    return ("exchange" in ctx.name or "cluster" in ctx.name
            or "dcn" in ctx.name or "shuffle" in ctx.name
            or "flight" in ctx.name)


def _peer_receive_sites(fn) -> List[ast.AST]:
    """Sites where peer-flight bytes land host-side: collecting the
    mailbox (``wait_flights`` / ``recv_peer_flight``), or a raw
    ``recv_framed`` inside a peer-named function (the gateway serve
    path). Plain ``recv_flight`` is exempt: its trailer is verified at
    the framing layer before decode (rule 15's seam)."""
    out: List[ast.AST] = []
    peer_fn = "peer" in fn.name.lower()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        last = _unparse(node.func).split(".")[-1]
        if last in ("wait_flights", "recv_peer_flight"):
            out.append(node)
        elif last == "recv_framed" and peer_fn:
            out.append(node)
    return out


def _fn_verifies_manifest(fn) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Raise):
            return True
        if not isinstance(node, ast.Call):
            continue
        text = _unparse(node.func)
        if ("verify" in text or "fingerprint" in text
                or text.split(".")[-1] == "compare_digest"):
            return True
    return False


def check_peer_flight_verifies_manifest(ctx: FileContext) -> List[RawFinding]:
    """ISSUE-20 bug class (rule 26): decode-before-verify on the direct
    exchange path. A peer flight arrives host-to-host — the supervisor
    never saw the bytes, so the manifest fingerprint (and the HMAC
    dial grant before it) is the ONLY thing standing between a merge
    and rows some other process injected or a blob corrupted past the
    ARQ budget. A function that collects peer flight bytes
    (``wait_flights`` mailbox collect, ``recv_peer_flight``, or a raw
    ``recv_framed`` in a peer-gateway serve path) but neither verifies
    (``verify*`` / ``*fingerprint*`` / ``hmac.compare_digest`` call)
    nor raises has broken verify-then-decode exactly where it matters
    most: the codec decodes attacker-reachable bytes and the corruption
    surfaces three layers up as wrong query results instead of a
    classified ``CorruptDataError`` naming the flight. Scope:
    exchange-/cluster-/dcn-/shuffle-/flight-named files."""
    if not _is_peer_flight_scope_file(ctx):
        return []
    out: List[RawFinding] = []
    for fn in _top_functions(ctx.tree):
        sites = _peer_receive_sites(fn)
        if not sites or _fn_verifies_manifest(fn):
            continue
        for node in sites:
            out.append(RawFinding(
                node.lineno, node.col_offset,
                f"`{_unparse(node)[:60]}` receives peer flight bytes in "
                f"`{fn.name}` but nothing verifies them against the "
                f"manifest: check the blob fingerprint (or the dial "
                f"grant via hmac.compare_digest) and raise before any "
                f"decode — an unverified peer flight lets corrupt or "
                f"injected bytes reach the codec and surface as wrong "
                f"merge results instead of a classified CorruptDataError"))
    return out


RULES = [
    Rule("no-host-transfer-in-device-path",
         "no np.asarray / jax.device_get / .tolist() / float(traced) "
         "inside jit scope or ops/*_device.py functions",
         check_host_transfer),
    Rule("no-python-branch-on-traced",
         "no Python if/while on a traced value inside @jax.jit",
         check_python_branch),
    Rule("sentinel-safety",
         "iinfo/finfo(...).max as a data sentinel requires an adjacent "
         "domain guard",
         check_sentinel_safety),
    Rule("padding-byte-invariant",
         "regex device bytesets must never contain byte 0 (the row "
         "padding byte)",
         check_padding_byte),
    Rule("dtype-width-discipline",
         "no implicit int32/int64 mixing in ops/ arithmetic",
         check_dtype_width),
    Rule("bitmask-via-helpers",
         "validity masks come from counts or columnar/bitmask.py, not "
         "ad-hoc != 0 tests",
         check_bitmask_helpers),
    Rule("fallback-must-be-recorded",
         "except ...Unsupported handlers and explicit host-engine pins "
         "in ops files must call telemetry.record_fallback(...)",
         check_fallback_recorded),
    Rule("jit-via-dispatch",
         "batch-shaped ops in ops/ go through runtime/dispatch, not a "
         "direct @jax.jit / jax.jit(...) that recompiles per row count",
         check_jit_via_dispatch),
    Rule("pipeline-stage-host-transfer",
         "pipeline stage workers never block on device->host transfers; "
         "host bytes come from the readers' host-staged decode",
         check_pipeline_stage_host_transfer),
    Rule("fusion-region-host-sync",
         "no host materialization inside fused-region device functions; "
         "host values resolve from binding metadata at plan-build time",
         check_fusion_region_host_sync),
    Rule("error-must-classify",
         "broad `except Exception` on the runtime/parallel/device path "
         "must re-raise through the resilience taxonomy or visibly "
         "account for the swallow (record_* event, counter, log)",
         check_error_must_classify),
    Rule("server-telemetry-session-id",
         "telemetry record_* calls in server-scope files must carry "
         "session attribution (session= kwarg or session_scope block)",
         check_server_session_id),
    Rule("reservation-release-in-finally",
         "a limiter reserve/reserve_blocking grant paired with a release "
         "in the same function must release in a finally (or a "
         "re-raising except handler); success-only releases leak bytes",
         check_reservation_release),
    Rule("span-must-scope",
         "spans.span(...) / spans.child(...) must be acquired with a "
         "`with` statement (or decorator): a leaked open span corrupts "
         "the thread-local span stack and never emits",
         check_span_scope),
    Rule("payload-must-verify",
         "binary reads of managed payloads in runtime/parallel scope "
         "must go through the integrity verify seam; a raw fh.read() "
         "turns torn writes into garbage columns instead of a "
         "classified CorruptDataError",
         check_payload_verify),
    Rule("cache-key-must-fingerprint",
         "result-cache get/put keys must carry the input-content "
         "fingerprint half; signature-only keying serves stale results "
         "the moment the bound data changes",
         check_cache_key_fingerprint),
    Rule("compress-inside-seal",
         "sealed payloads in runtime/parallel scope must route through "
         "the runtime/compress codec seam before integrity.seal, and "
         "reads must verify before they decompress (the trailer covers "
         "the compressed bytes)",
         check_compress_inside_seal),
    Rule("worker-exit-must-classify",
         "supervision code that consumes a worker subprocess exit "
         "status (.returncode, used .wait()/.poll(), os.waitpid) must "
         "route the shape through resilience.classify_worker_exit / a "
         "classify call, raise, or visibly account for the read",
         check_worker_exit_classified),
    Rule("placement-must-record",
         "a placement-named function in a fleet/cluster file that "
         "selects among candidates (min/max/sorted/random.*) must "
         "record the routing decision: record_* event, counter "
         ".inc(), or raise",
         check_placement_recorded),
    Rule("rtfilter-decision-must-record",
         "a decision-named function in an rtfilter file that gates or "
         "sizes a runtime filter (threshold compare / optimal_params) "
         "must record the decision with a reason: record_rtfilter, "
         "counter .inc(), or raise",
         check_rtfilter_decision_recorded),
    Rule("exchange-overflow-must-classify",
         "a function in an exchange/shuffle file that branches on an "
         "overflow flag must classify it (classify_overflow / "
         "resilience.escalate -> CapacityOverflow) or raise — never a "
         "bare-boolean drop/cap path",
         check_exchange_overflow_classified),
    Rule("peer-flight-must-verify-manifest",
         "a function in an exchange/cluster/dcn/shuffle file that "
         "collects peer flight bytes (wait_flights / recv_peer_flight "
         "/ peer-path recv_framed) must verify them against the "
         "manifest fingerprint or dial grant (verify*/fingerprint/"
         "compare_digest) or raise — never decode-before-verify",
         check_peer_flight_verifies_manifest),
]
