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
