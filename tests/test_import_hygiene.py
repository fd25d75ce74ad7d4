"""Import-order hygiene: the package must be importable BEFORE a platform
pin without initializing any jax backend.

tests/conftest.py, __graft_entry__.dryrun_multichip and a fleet
supervisor all do ``import spark_rapids_jni_tpu...`` and only
then call ``force_cpu_platform()``. That is only sound while nothing in the
package's import graph creates a jax array / queries devices at module
level — the moment one does, the default backend (on a TPU machine: the
chip, which belongs to one process) would initialize first and the pin
would silently stop working. This test pins that invariant mechanically.
"""

import ast
import pathlib
import subprocess
import sys

_CODE = """
import spark_rapids_jni_tpu
import spark_rapids_jni_tpu.utils.platform
from jax._src import xla_bridge
assert not xla_bridge._backends, (
    "package import initialized jax backends: %r" % (xla_bridge._backends,)
)
print("IMPORT_CLEAN")
"""


def test_package_import_initializes_no_backend():
    out = subprocess.run(
        [sys.executable, "-c", _CODE],
        capture_output=True,
        text=True,
        timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "IMPORT_CLEAN" in out.stdout


def test_no_operator_imports_a_query_or_the_server():
    """``ops/`` is what ``models/`` and ``runtime/server.py`` are built
    from: an operator that imports either has a query's or the served
    path's name in it. Every import statement counts, a function's own
    too."""
    package = pathlib.Path(__file__).parent.parent / "spark_rapids_jni_tpu"
    upward = ("spark_rapids_jni_tpu.models",
              "spark_rapids_jni_tpu.runtime.server")
    found = []
    for path in sorted((package / "ops").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module] + [
                    f"{node.module}.{a.name}" for a in node.names]
            else:
                continue
            found += [f"{path.name}:{node.lineno} {name}" for name in names
                      if name.startswith(upward)]
    assert not found
