"""Everything that belongs to one cell is found by its name, never by an
edit: ``module(kind, name)`` loads ``benchmark/<kind>/<name>.py`` and
``data(kind, name)`` reads ``benchmark/<kind>/<name>.json``. A later PR
adds a plan, a loop, a table maker, a freshener or a layer metric as one
more file of that kind."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")


def _path(kind: str, name: str, ext: str) -> str:
    if not _NAME.match(name) or not _NAME.match(kind):
        raise LookupError(f"{kind} name {name!r} is not a benchmark name")
    path = os.path.join(HERE, kind, name + ext)
    if not os.path.isfile(path):
        raise LookupError(
            f"no {kind} named {name!r}: expected benchmark/{kind}/{name}{ext}")
    return path


def module(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py`` (names may hold dots, so
    it is loaded from its path and not imported by a dotted name)."""
    modname = "benchmark.{}.{}".format(kind, re.sub(r"[.\-]", "_", name))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(
        modname, _path(kind, name, ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def data(kind: str, name: str) -> dict:
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def spec() -> dict:
    """``BENCHMARK.json`` of the checkout."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(workload: str, bench: dict) -> tuple:
    """(workload entry, configuration file's content, mix file's content)."""
    for w in bench["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise LookupError(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    for c in bench["configs"]:
        if c["name"] == w["config"]:
            with open(os.path.join(ROOT, c["file"])) as f:
                config = json.load(f)
            break
    else:
        raise LookupError(f"workload {workload!r} names no known config")
    return w, config, data("mixes", w["traffic"])
