"""chip_smoke.py on the CPU, tiny: its phase functions pass, a missing chip
and a bare directory fail it, the counter check sees a recovery rung that
fired even though the results still match — and, beside it, the compile
cache contract (JAX_COMPILATION_CACHE_DIR or the one fixed path; a second
process hits).

The platform the phases assert is passed in as an argument ("cpu" here,
"tpu" in chip_smoke.py's own children), never read from the environment.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.runtime import dispatch, faults, fusion
from spark_rapids_jni_tpu.telemetry import REGISTRY
from spark_rapids_jni_tpu.utils import config
from spark_rapids_jni_tpu.utils.config import reset_option

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# TPC-H shapes, toy scale: control flow and checks, not sizes
TINY = dict(chip_smoke.FULL, sf10_rows=3000, sf1_rows=5000, customers=40,
            orders=300, cluster_orders=600,
            cluster_customers=60)
CPU = {"platform": "cpu", "kind": "cpu", "count": 2}


@pytest.fixture(autouse=True)
def _isolated():
    dispatch.clear()
    REGISTRY.reset()
    yield
    reset_option("telemetry.enabled")  # every phase switches it on
    dispatch.clear()
    REGISTRY.reset()


# ---------------------------------------------------------------------------
# the phase functions, tiny
# ---------------------------------------------------------------------------


def test_serve_phase_passes_tiny_on_cpu(tmp_path):
    report = chip_smoke.serve_phase(TINY, "cpu", scratch=str(tmp_path))
    # planned q1, q6 at the "SF10" size; general q1, parquet q1, both q3
    # plans at the "SF1" size; every one cold, second and repeated
    assert {k.split("@")[0] for k in report} == {
        "q1_planned", "q6", "q1_general", "q1_parquet", "q3_general",
        "q3_planned", "sync", "fingerprint"}
    # both readings are there; their ratio is judged on the chip only
    assert all(isinstance(report["sync"][k], float) and report["sync"][k] > 0
               for k in ("one_s", "four_s"))
    # the table where it lives against numpy over its host copy (tiny: all
    # of it under the digest's threshold, so none digested on the device)
    assert report["fingerprint"]["moved"] == {
        "cache.fingerprint_bytes": 5000 * 38,
        "cache.fingerprint_device_bytes": 0}
    assert not list(tmp_path.iterdir())  # the Parquet files are removed


def test_fleet_phase_each_replica_serves_a_checked_query():
    report = chip_smoke.fleet_phase(TINY, "cpu", CPU)
    assert report["replicas"] == 2
    assert [d["platform"] for d in report["devices"]] == ["cpu", "cpu"]


# The two phases that need four chips are slow-tier (~10 s each of
# shard_map and exchange compiles): tier-1 is cut off at 870 s, and what they
# drive is tier-1 already in test_parallel.py, test_distributed_bounded.py
# and test_exchange.py (the q13 mid-plan exchange over the mesh).


@pytest.mark.slow
def test_mesh_phase_passes_on_virtual_devices():
    # conftest's 8 virtual CPU devices stand in for the four chips
    report = chip_smoke.mesh_phase(TINY, "cpu")
    assert report["chips"] == 4


@pytest.mark.slow
def test_cluster_phase_passes_with_cpu_hosts():
    report = chip_smoke.cluster_phase(TINY, "cpu", CPU, hosts=2)
    assert report["hosts"] == 2


def test_phases_refuse_a_platform_they_were_not_given():
    with pytest.raises(chip_smoke.SmokeFailure, match="no chip found"):
        chip_smoke.probe_phase("tpu")


# ---------------------------------------------------------------------------
# no quiet way off the chip
# ---------------------------------------------------------------------------


def test_counter_check_fails_when_compile_fell_back_inline():
    """A fault at the dispatch.compile seam: dispatch.call runs the region
    eagerly instead, the result still equals the oracle — and the smoke's
    counter check still fails the run, naming the counter."""
    config.set_option("telemetry.enabled", True)
    li = tpch.lineitem_table(500, seed=3)
    chip_smoke.check_counters()  # clean slate passes
    with faults.inject(faults.FaultScript([faults.FaultSpec(
            "dispatch.compile", RuntimeError("injected compile failure"))])):
        out = fusion.execute(tpch._q6_plan(), {"lineitem": li})
    chip_smoke.check_q6(out.table, tpch.tpch_q6_numpy(li), "q6 inline")
    with pytest.raises(chip_smoke.SmokeFailure,
                       match=r"dispatch\.compile_error = 1"):
        chip_smoke.check_counters()


def test_counter_check_reads_tickets_and_fusion_fallbacks():
    chip_smoke.check_counters(tickets=[("q", ("fused", 0, 0))])
    with pytest.raises(chip_smoke.SmokeFailure, match="ticket q"):
        chip_smoke.check_counters(tickets=[("q", ("staged", 1, 1))])
    REGISTRY.counter("fallback.fusion.tpch_q6").inc()
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="fallback.fusion.tpch_q6"):
        chip_smoke.check_counters()


def test_served_ticket_records_where_it_finished():
    from spark_rapids_jni_tpu.runtime.server import QueryServer

    li = tpch.lineitem_table(300, seed=1)
    with QueryServer() as srv:
        first = srv.session("s").submit(tpch._q6_plan(), {"lineitem": li})
        first.result(timeout=60)
        again = srv.session("s").submit(tpch._q6_plan(), {"lineitem": li})
        again.result(timeout=60)
    assert (first.tier, first.rung, first.steps) == ("fused", 0, 0)
    assert (again.tier, again.rung, again.steps) == (None, None, None)
    assert again.queue_wait_s == 0  # the result-cache hit never executed


# ---------------------------------------------------------------------------
# the script itself: no chip, or nothing of the repo beside it
# ---------------------------------------------------------------------------


def _run_smoke(cwd, script, env):
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_exits_nonzero_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run_smoke(REPO, os.path.join(REPO, "chip_smoke.py"), env)
    assert out.returncode != 0
    assert "no chip found" in out.stderr
    assert '"ok"' not in out.stdout
    assert "serve" not in out.stdout  # nothing ran on the CPU


def test_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _run_smoke(str(tmp_path), str(tmp_path / "chip_smoke.py"), env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_parent_never_imports_jax():
    code = ("import sys, chip_smoke; "
            "assert 'jax' not in sys.modules and "
            "'spark_rapids_jni_tpu' not in sys.modules; print('CLEAN')")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and "CLEAN" in out.stdout, out.stderr


# ---------------------------------------------------------------------------
# the compile cache can be placed from outside
# ---------------------------------------------------------------------------

_CACHE_PROBE = """
import json, os, sys
import jax
from jax import monitoring
import spark_rapids_jni_tpu
from spark_rapids_jni_tpu.models import tpch
from spark_rapids_jni_tpu.runtime import fusion
from spark_rapids_jni_tpu.utils.config import FIXED_CACHE_DIR, cache_dir
events = {}
monitoring.register_event_listener(
    lambda event, **kw: events.__setitem__(event, events.get(event, 0) + 1))
if len(sys.argv) > 1:
    out = fusion.execute(tpch._q6_plan(),
                         {"lineitem": tpch.lineitem_table(400, seed=9)})
    jax.block_until_ready(out.table.column(0).data)
from spark_rapids_jni_tpu.runtime.rtfilter import _SelectivityStore
from spark_rapids_jni_tpu.runtime.server import QueryServer
print(json.dumps({
    "configured": jax.config.jax_compilation_cache_dir,
    "cache_dir": cache_dir(), "fixed": FIXED_CACHE_DIR,
    "estimates": QueryServer._resolve_estimate_path(),
    "selectivity": _SelectivityStore._resolve_path(),
    "hits": events.get("/jax/compilation_cache/cache_hits", 0)}))
"""


def _cache_probe(env_extra, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_ENABLE_COMPILATION_CACHE",
                        "JAX_COMPILATION_CACHE_DIR",
                        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE, *args],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_dir_from_environment_is_the_whole_mechanism(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, no code sets another
    directory, and a second process hits what the first compiled."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
           # JAX's own threshold: a sub-second CPU compile persists too
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    first = _cache_probe(env, "run")
    assert first["configured"] == first["cache_dir"] == str(tmp_path / "cc")
    assert first["hits"] == 0
    assert any(f.endswith("-cache") for f in os.listdir(tmp_path / "cc"))
    second = _cache_probe(env, "run")
    assert second["hits"] >= 1
    # the stores that sit beside the cache resolve through the same function
    assert first["estimates"] == str(tmp_path / "cc" / "learned_estimates.json")
    assert first["selectivity"] == str(
        tmp_path / "cc" / "learned_selectivity.json")


def test_cache_dir_unset_is_the_one_fixed_path():
    got = _cache_probe({})  # imports only: nothing compiled, nothing written
    assert got["configured"] == got["cache_dir"] == got["fixed"]
    assert got["fixed"] == os.path.join(REPO, ".jax_cache")
    assert os.path.dirname(got["estimates"]) == got["fixed"]


def test_jax_switch_off_persists_nothing():
    got = _cache_probe({"JAX_ENABLE_COMPILATION_CACHE": "false"})
    assert got["cache_dir"] == got["estimates"] == got["selectivity"] == ""


def test_pytest_cache_lives_outside_the_checkout():
    # tests/conftest.py: a temporary directory for this process and every
    # worker it boots, placed through JAX's own variable
    assert config.cache_dir() == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert not config.cache_dir().startswith(REPO)
