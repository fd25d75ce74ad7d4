"""tpulint: the AST invariant linter (tools/tpulint).

Two halves:

1. **Rule regression** — each seeded-violation fixture under
   tests/tpulint_fixtures/ must produce exactly its rule's findings
   (and none on the clean counterparts in the same file).
2. **Whole-tree gate** — linting spark_rapids_jni_tpu + tools with
   the checked-in baseline must be clean, both through the
   library and through the real CLI (`python -m tools.tpulint`), which
   is what ci/lint.sh runs.

The linter is pure stdlib ast — no jax import, so this whole file is
fast-tier.
"""

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.tpulint.engine import (  # noqa: E402
    Finding,
    apply_baseline,
    baseline_key,
    lint_paths,
    lint_source,
    load_baseline,
    write_baseline,
)
from tools.tpulint.rules import RULES  # noqa: E402

FIXTURES = REPO / "tests" / "tpulint_fixtures"
RULE_NAMES = {r.name for r in RULES}


def _lint_file(path: Path):
    return lint_source(path.read_text(), path)


def _by_rule(findings, rule):
    assert rule in RULE_NAMES, rule
    return [f for f in findings if f.rule == rule]


# ---------------------------------------------------------------------------
# seeded-violation fixtures, one per rule
# ---------------------------------------------------------------------------


def test_rule_host_transfer_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_host_transfer_device.py"),
                   "no-host-transfer-in-device-path")
    texts = [f.source_line for f in got]
    assert len(got) == 3, texts
    assert any("np.asarray" in t for t in texts)
    assert any(".tolist()" in t for t in texts)
    assert any("float(" in t for t in texts)
    # the clean jnp.asarray construction must NOT be flagged
    assert not any("jnp.asarray" in t for t in texts)


def test_rule_python_branch_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_python_branch.py"),
                   "no-python-branch-on-traced")
    texts = [f.source_line for f in got]
    assert len(got) == 2, texts
    assert any(t.startswith("if total") for t in texts)
    assert any(t.startswith("while total") for t in texts)
    # static_argnames params, .shape reads and host functions stay legal
    assert not any("flip" in t or "shape" in t for t in texts)


def test_rule_sentinel_safety_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_sentinel.py"),
                   "sentinel-safety")
    assert len(got) == 1, got
    # the violation is in unguarded_sentinel; the guarded twin passes
    src = (FIXTURES / "seeded_sentinel.py").read_text()
    guarded_at = src[:src.index("def guarded_sentinel")].count("\n") + 1
    assert got[0].line < guarded_at


def test_rule_padding_byte_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_regex_nul_device.py"),
                   "padding-byte-invariant")
    texts = [f.source_line for f in got]
    assert len(got) == 3, texts
    assert not any("SAFE" in t for t in texts)


def test_rule_padding_byte_needs_regex_device_filename(tmp_path):
    # same constructions outside a regex *_device.py are out of scope
    target = tmp_path / "not_a_regex_file.py"
    shutil.copy(FIXTURES / "seeded_regex_nul_device.py", target)
    assert not _by_rule(_lint_file(target), "padding-byte-invariant")


def test_rule_dtype_width_seeded(tmp_path):
    # the rule keys off an ops/ path segment
    ops_dir = tmp_path / "ops"
    ops_dir.mkdir()
    target = ops_dir / "seeded_dtype_width.py"
    shutil.copy(FIXTURES / "seeded_dtype_width.py", target)
    got = _by_rule(_lint_file(target), "dtype-width-discipline")
    assert len(got) == 1, got
    assert "rows * stride" in got[0].source_line
    # out of ops/: silent
    flat = tmp_path / "seeded_dtype_width.py"
    shutil.copy(FIXTURES / "seeded_dtype_width.py", flat)
    assert not _by_rule(_lint_file(flat), "dtype-width-discipline")


def test_rule_bitmask_helpers_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_bitmask.py"),
                   "bitmask-via-helpers")
    assert len(got) == 1, got
    assert "sums != 0" in got[0].source_line
    # count-derived presence (counts > 0) is the blessed form
    assert "counts" not in got[0].source_line


def test_rule_fallback_recorded_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_fallback_device.py"),
                   "fallback-must-be-recorded")
    texts = [f.source_line for f in got]
    assert len(got) == 2, texts
    assert any("except RegexUnsupported:" in t for t in texts)
    assert any('force == "host"' in t for t in texts)
    # the recorded twins and the pure re-raise handler stay clean
    lines = [f.line for f in got]
    src = (FIXTURES / "seeded_fallback_device.py").read_text()
    clean_at = src[:src.index("def recorded_swallow")].count("\n") + 1
    assert all(ln < clean_at for ln in lines), lines


def test_rule_fallback_recorded_needs_ops_or_device_scope(tmp_path):
    # same constructions outside ops/ or a *_device.py file are out of
    # scope: host-side orchestration may legitimately branch on "host"
    target = tmp_path / "not_an_ops_file.py"
    shutil.copy(FIXTURES / "seeded_fallback_device.py", target)
    assert not _by_rule(_lint_file(target), "fallback-must-be-recorded")


def test_rule_jit_via_dispatch_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_dispatch_device.py"),
                   "jit-via-dispatch")
    texts = [f.source_line for f in got]
    assert len(got) == 2, texts
    assert any(t.startswith("@jax.jit") for t in texts)
    assert any("jax.jit(lambda" in t for t in texts)
    # the pragma'd deliberate jit and the dispatch.rowwise twin stay clean
    src = (FIXTURES / "seeded_dispatch_device.py").read_text()
    clean_at = src[:src.index("def pragmaed_kernel")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_jit_via_dispatch_needs_ops_or_device_scope(tmp_path):
    # a direct jit outside ops/ or a *_device.py file is host-side
    # orchestration (bench drivers, runtime/dispatch itself) — out of scope
    target = tmp_path / "not_an_ops_file.py"
    shutil.copy(FIXTURES / "seeded_dispatch_device.py", target)
    assert not _by_rule(_lint_file(target), "jit-via-dispatch")
    # under an ops/ segment the same source fires regardless of basename
    ops_dir = tmp_path / "ops"
    ops_dir.mkdir()
    target2 = ops_dir / "plain_name.py"
    shutil.copy(FIXTURES / "seeded_dispatch_device.py", target2)
    assert _by_rule(_lint_file(target2), "jit-via-dispatch")


def test_rule_pipeline_stage_host_transfer_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_pipeline_stage.py"),
                   "pipeline-stage-host-transfer")
    texts = [f.source_line for f in got]
    assert len(got) == 4, texts
    assert any("jax.device_get" in t for t in texts)
    assert any("np.asarray" in t for t in texts)
    assert any("block_until_ready" in t for t in texts)
    assert any(".item()" in t for t in texts)
    # the host-staged twin and the pragma'd bounded probe stay clean
    src = (FIXTURES / "seeded_pipeline_stage.py").read_text()
    clean_at = src[:src.index("def clean_host_staged")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_pipeline_stage_needs_pipeline_filename(tmp_path):
    # same constructions outside a pipeline module are host-side
    # orchestration (bench drivers, notebooks) — out of scope
    target = tmp_path / "plain_orchestration.py"
    shutil.copy(FIXTURES / "seeded_pipeline_stage.py", target)
    assert not _by_rule(_lint_file(target), "pipeline-stage-host-transfer")


def test_rule_fusion_region_host_sync_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_fusion_region.py"),
                   "fusion-region-host-sync")
    texts = [f.source_line for f in got]
    assert len(got) == 4, texts
    assert any("np.asarray" in t for t in texts)
    assert any("jax.device_get" in t for t in texts)
    assert any("block_until_ready" in t for t in texts)
    assert any(".item()" in t for t in texts)
    # metadata-derived plan building and the pragma'd boundary read stay
    # clean
    src = (FIXTURES / "seeded_fusion_region.py").read_text()
    clean_at = src[:src.index("def clean_plan_build")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_fusion_region_needs_fusion_filename(tmp_path):
    # same constructions outside a fusion module are host-side
    # orchestration (bench drivers, result consumers) — out of scope
    target = tmp_path / "plain_orchestration.py"
    shutil.copy(FIXTURES / "seeded_fusion_region.py", target)
    assert not _by_rule(_lint_file(target), "fusion-region-host-sync")


def test_rule_error_must_classify_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_resilience_swallow.py"),
                   "error-must-classify")
    texts = [f.source_line for f in got]
    assert len(got) == 3, texts
    assert sum("except Exception" in t for t in texts) == 2
    assert any(t.startswith("except:") for t in texts)
    # recorded/re-raising/logged/narrow/unwind/pragma'd twins stay clean
    src = (FIXTURES / "seeded_resilience_swallow.py").read_text()
    clean_at = src[:src.index("def recorded_swallow")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_error_must_classify_scope(tmp_path):
    # same constructions outside resilience/faults/runtime/parallel scope
    # are host-side best-effort code — out of scope
    target = tmp_path / "plain_orchestration.py"
    shutil.copy(FIXTURES / "seeded_resilience_swallow.py", target)
    assert not _by_rule(_lint_file(target), "error-must-classify")
    # under a runtime/ path segment the same source fires regardless of
    # basename — the rule guards the whole execution path, not a filename
    rt = tmp_path / "runtime"
    rt.mkdir()
    target2 = rt / "plain_name.py"
    shutil.copy(FIXTURES / "seeded_resilience_swallow.py", target2)
    assert _by_rule(_lint_file(target2), "error-must-classify")


def test_rule_server_session_id_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_server_telemetry.py"),
                   "server-telemetry-session-id")
    texts = [f.source_line for f in got]
    assert len(got) == 3, texts
    assert sum("record_server" in t for t in texts) == 1
    assert sum("record_fallback" in t for t in texts) == 1
    assert sum("record_spill" in t for t in texts) == 1
    # kwarg / session_scope / splat / pragma'd twins stay clean
    src = (FIXTURES / "seeded_server_telemetry.py").read_text()
    clean_at = src[:src.index("def clean_explicit_session")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_server_session_id_scope(tmp_path):
    # the identical source under a non-server basename is out of scope:
    # host-side scripts emit events the ambient platform tags suffice for
    target = tmp_path / "plain_batch_job.py"
    shutil.copy(FIXTURES / "seeded_server_telemetry.py", target)
    assert not _by_rule(_lint_file(target), "server-telemetry-session-id")


def test_rule_reservation_release_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_reservation_memory.py"),
                   "reservation-release-in-finally")
    texts = [f.source_line for f in got]
    assert len(got) == 2, texts
    assert any("limiter.reserve(nbytes)" in t for t in texts)
    assert any("reserve_blocking" in t for t in texts)
    # finally-released, unwind-transfer, ownership-transfer, nested-worker,
    # lock-release and pragma'd twins stay clean
    src = (FIXTURES / "seeded_reservation_memory.py").read_text()
    clean_at = src[:src.index("def clean_release_in_finally")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_reservation_release_scope(tmp_path):
    # same constructions outside memory/server/degrade/outofcore basenames
    # or runtime//parallel/ paths are host-side orchestration — out of scope
    target = tmp_path / "plain_batch_job.py"
    shutil.copy(FIXTURES / "seeded_reservation_memory.py", target)
    assert not _by_rule(_lint_file(target), "reservation-release-in-finally")
    # under a runtime/ path segment the same source fires regardless of
    # basename — the rule guards the budget-accounting path, not a filename
    rt = tmp_path / "runtime"
    rt.mkdir()
    target2 = rt / "plain_name.py"
    shutil.copy(FIXTURES / "seeded_reservation_memory.py", target2)
    assert _by_rule(_lint_file(target2), "reservation-release-in-finally")


def test_rule_span_scope_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_span_scope.py"),
                   "span-must-scope")
    texts = [f.source_line for f in got]
    assert len(got) == 4, texts
    assert any("spans.span" in t for t in texts)
    assert any("spans.child" in t for t in texts)
    assert any("span(" in t and "handle" in t for t in texts)
    assert any("child(" in t and "c =" in t for t in texts)
    # with-scoped, aliased-with, unrelated-attr and pragma'd twins stay clean
    src = (FIXTURES / "seeded_span_scope.py").read_text()
    clean_at = src[:src.index("def clean_with_scope")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_span_scope_ignores_files_without_spans_import(tmp_path):
    # .span()/.child() on arbitrary objects in files that never import
    # telemetry.spans are someone else's API — out of scope
    target = tmp_path / "other.py"
    target.write_text(
        "def f(tracer):\n"
        "    probe = tracer.span('x')\n"
        "    return tracer.child('y'), probe\n")
    assert not _by_rule(_lint_file(target), "span-must-scope")


def test_rule_payload_verify_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_payload_memory.py"),
                   "payload-must-verify")
    texts = [f.source_line for f in got]
    assert len(got) == 2, texts
    assert any("blob = fh.read()" in t for t in texts)
    assert any("fh.read(16)" in t for t in texts)
    # verified-read, read-then-verify, text-mode, write-mode and pragma'd
    # twins stay clean
    src = (FIXTURES / "seeded_payload_memory.py").read_text()
    clean_at = src[:src.index("def clean_verified_read")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_payload_verify_scope(tmp_path):
    # same constructions outside the reservation scope are ordinary file
    # IO — out of scope; integrity.py itself (the seam's home) is exempt
    target = tmp_path / "plain_loader.py"
    shutil.copy(FIXTURES / "seeded_payload_memory.py", target)
    assert not _by_rule(_lint_file(target), "payload-must-verify")
    rt = tmp_path / "runtime"
    rt.mkdir()
    target2 = rt / "plain_name.py"
    shutil.copy(FIXTURES / "seeded_payload_memory.py", target2)
    assert _by_rule(_lint_file(target2), "payload-must-verify")
    target3 = rt / "integrity.py"
    shutil.copy(FIXTURES / "seeded_payload_memory.py", target3)
    assert not _by_rule(_lint_file(target3), "payload-must-verify")


def test_rule_cache_key_fingerprint_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_resultcache_key.py"),
                   "cache-key-must-fingerprint")
    texts = [f.source_line for f in got]
    assert len(got) == 4, texts
    assert any("cache.get(sig)" in t for t in texts)
    assert any("plan_signature(plan, bindings)" in t for t in texts)
    assert any("CacheKey(sig))" in t for t in texts)
    assert any('CacheKey(sig, "")' in t for t in texts)
    # derived-key, full-CacheKey, source-fingerprint, non-cache-receiver
    # and pragma'd twins stay clean
    src = (FIXTURES / "seeded_resultcache_key.py").read_text()
    clean_at = src[:src.index("def clean_derived_key")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_cache_key_fingerprint_scope(tmp_path):
    # same constructions outside cache/reservation scope are someone
    # else's get/put contract — out of scope
    target = tmp_path / "plain_store.py"
    shutil.copy(FIXTURES / "seeded_resultcache_key.py", target)
    assert not _by_rule(_lint_file(target), "cache-key-must-fingerprint")
    rt = tmp_path / "runtime"
    rt.mkdir()
    target2 = rt / "plain_name.py"
    shutil.copy(FIXTURES / "seeded_resultcache_key.py", target2)
    assert _by_rule(_lint_file(target2), "cache-key-must-fingerprint")


def test_rule_compress_inside_seal_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_compress_memory.py"),
                   "compress-inside-seal")
    texts = [f.source_line for f in got]
    assert len(got) == 3, texts
    assert any("integrity.seal(payload)" in t for t in texts)
    assert any("write_payload_file" in t for t in texts)
    assert any("decode_array" in t for t in texts)
    # verify-then-decode, decode-only and pragma'd twins stay clean
    src = (FIXTURES / "seeded_compress_memory.py").read_text()
    clean_at = src[:src.index("def clean_verify_then_decode")].count(
        "\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_compress_inside_seal_scope(tmp_path):
    # same constructions outside the reservation scope are out of scope;
    # the codec's own home (a compress basename) is exempt
    target = tmp_path / "plain_tool.py"
    shutil.copy(FIXTURES / "seeded_compress_memory.py", target)
    assert not _by_rule(_lint_file(target), "compress-inside-seal")
    rt = tmp_path / "runtime"
    rt.mkdir()
    target2 = rt / "plain_name.py"
    shutil.copy(FIXTURES / "seeded_compress_memory.py", target2)
    assert _by_rule(_lint_file(target2), "compress-inside-seal")
    target3 = rt / "compress.py"
    shutil.copy(FIXTURES / "seeded_compress_memory.py", target3)
    assert not _by_rule(_lint_file(target3), "compress-inside-seal")


def test_rule_compress_inside_seal_codec_reference_trusted(tmp_path):
    # a sealing module that references the codec anywhere is trusted at
    # module granularity (dcn's send path seals a blob its serializer
    # already compressed)
    rt = tmp_path / "runtime"
    rt.mkdir()
    mod = rt / "memory_like.py"
    mod.write_text(
        "from spark_rapids_jni_tpu.runtime import compress\n"
        "\n"
        "\n"
        "def spill(integrity, path, arr):\n"
        "    blob = integrity.seal(compress.encode_array(arr))\n"
        "    integrity.write_payload_file(path, blob)\n")
    assert not _by_rule(_lint_file(mod), "compress-inside-seal")


def test_rule_worker_exit_classified_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_fleet_worker_exit.py"),
                   "worker-exit-must-classify")
    texts = [f.source_line for f in got]
    assert len(got) == 4, texts
    assert any(".returncode" in t for t in texts)
    assert any("proc.wait" in t for t in texts)
    assert any("worker.poll" in t for t in texts)
    assert any("os.waitpid" in t for t in texts)
    # classified / recorded / raising / join-barrier / Event.wait /
    # pragma'd twins past the clean_ marker all stay clean
    src = (FIXTURES / "seeded_fleet_worker_exit.py").read_text()
    clean_at = src[:src.index("def clean_classified_reap")].count("\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_worker_exit_classified_scope(tmp_path):
    # same constructions outside the supervision scope are out of scope;
    # a fleet-named file anywhere is in scope (the rule's home)
    target = tmp_path / "plain_tool.py"
    shutil.copy(FIXTURES / "seeded_fleet_worker_exit.py", target)
    assert not _by_rule(_lint_file(target), "worker-exit-must-classify")
    rt = tmp_path / "runtime"
    rt.mkdir()
    target2 = rt / "plain_name.py"
    shutil.copy(FIXTURES / "seeded_fleet_worker_exit.py", target2)
    assert _by_rule(_lint_file(target2), "worker-exit-must-classify")


def test_rule_worker_exit_join_barrier_clean(tmp_path):
    # a bare-expression proc.wait() used purely as a join barrier never
    # consumes the status: exempt even with zero accounting around it
    rt = tmp_path / "runtime"
    rt.mkdir()
    mod = rt / "fleet_like.py"
    mod.write_text(
        "def shutdown(replicas):\n"
        "    for r in replicas:\n"
        "        r.proc.wait(timeout=5.0)\n")
    assert not _by_rule(_lint_file(mod), "worker-exit-must-classify")


def test_rule_placement_recorded_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_cluster_placement.py"),
                   "placement-must-record")
    texts = [f.source_line for f in got]
    assert len(got) == 4, texts
    assert any("min(replicas" in t for t in texts)
    assert any("sorted(hosts" in t for t in texts)
    assert any("random.choice" in t for t in texts)
    assert any("max(live" in t for t in texts)
    # counted / recorded / raising / arithmetic-only / pragma'd /
    # unrelated-name twins past the clean_ marker all stay clean
    src = (FIXTURES / "seeded_cluster_placement.py").read_text()
    clean_at = src[:src.index("def clean_pick_replica_counted")].count(
        "\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_placement_recorded_scope(tmp_path):
    # the same silent selections outside a fleet/cluster-named file are
    # out of scope — even inside runtime/ (a generic chooser is not a
    # placement decision); cluster- and fleet-named files are in scope
    src = (FIXTURES / "seeded_cluster_placement.py").read_text()
    rt = tmp_path / "runtime"
    rt.mkdir()
    plain = rt / "compress_like.py"
    plain.write_text(src)
    assert not _by_rule(_lint_file(plain), "placement-must-record")
    fleety = rt / "fleet_like.py"
    fleety.write_text(src)
    assert _by_rule(_lint_file(fleety), "placement-must-record")


def test_rule_placement_recorded_shipping_code_complies():
    # the real routers must hold their own rule: every placement site in
    # runtime/fleet.py and runtime/cluster.py records its decision
    for mod in ("fleet", "cluster"):
        path = REPO / "spark_rapids_jni_tpu" / "runtime" / f"{mod}.py"
        assert not _by_rule(_lint_file(path), "placement-must-record"), mod


def test_rule_rtfilter_decision_recorded_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_rtfilter_decision.py"),
                   "rtfilter-decision-must-record")
    texts = [f.source_line for f in got]
    assert len(got) == 4, texts
    assert any("build_rows > max_rows" in t for t in texts)
    assert any("ema <= threshold" in t for t in texts)
    assert any("optimal_params(expected" in t for t in texts)
    assert any("rows < 8" in t for t in texts)
    # recorded / counted / raising / pragma'd / arithmetic-only /
    # unrelated-name twins past the clean_ marker all stay clean
    src = (FIXTURES / "seeded_rtfilter_decision.py").read_text()
    clean_at = src[:src.index("def clean_decide_recorded")].count(
        "\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_rtfilter_decision_recorded_scope(tmp_path):
    # the same silent gates outside an rtfilter-named file are out of
    # scope — even inside runtime/ (fusion.py's injection pass delegates
    # its choices to rtfilter.decide, which is where the rule holds)
    src = (FIXTURES / "seeded_rtfilter_decision.py").read_text()
    rt = tmp_path / "runtime"
    rt.mkdir()
    plain = rt / "fusion_like.py"
    plain.write_text(src)
    assert not _by_rule(_lint_file(plain), "rtfilter-decision-must-record")
    filtery = rt / "rtfilter_like.py"
    filtery.write_text(src)
    assert _by_rule(_lint_file(filtery), "rtfilter-decision-must-record")


def test_rule_rtfilter_decision_recorded_shipping_code_complies():
    # the real planner must hold its own rule: every gate/sizing site in
    # runtime/rtfilter.py records its decision with a reason
    path = REPO / "spark_rapids_jni_tpu" / "runtime" / "rtfilter.py"
    assert not _by_rule(_lint_file(path), "rtfilter-decision-must-record")


def test_rule_exchange_overflow_classified_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_exchange_overflow.py"),
                   "exchange-overflow-must-classify")
    texts = [f.source_line for f in got]
    assert len(got) == 3, texts
    assert any("if overflowed:" in t for t in texts)
    assert any("while overflowed" in t for t in texts)
    assert any("if overflow_flag" in t for t in texts)
    # classified / escalating / pragma'd / device-passthrough /
    # unrelated-branch twins past the clean_ marker all stay clean
    src = (FIXTURES / "seeded_exchange_overflow.py").read_text()
    clean_at = src[:src.index("def clean_pack_classified")].count(
        "\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_exchange_overflow_classified_scope(tmp_path):
    # the same bare-boolean branches outside an exchange/shuffle-named
    # file are out of scope — even inside runtime/ (a generic capacity
    # check is not an exchange overflow); shuffle-named files are in
    src = (FIXTURES / "seeded_exchange_overflow.py").read_text()
    rt = tmp_path / "runtime"
    rt.mkdir()
    plain = rt / "outofcore_like.py"
    plain.write_text(src)
    assert not _by_rule(_lint_file(plain), "exchange-overflow-must-classify")
    shuffley = rt / "shuffle_like.py"
    shuffley.write_text(src)
    assert _by_rule(_lint_file(shuffley), "exchange-overflow-must-classify")


def test_rule_exchange_overflow_classified_shipping_code_complies():
    # the real exchange paths must hold their own rule: every overflow
    # branch in runtime/exchange.py and parallel/shuffle.py classifies
    for rel in (("runtime", "exchange.py"), ("parallel", "shuffle.py")):
        path = REPO / "spark_rapids_jni_tpu" / rel[0] / rel[1]
        assert not _by_rule(_lint_file(path),
                            "exchange-overflow-must-classify"), rel


def test_rule_peer_flight_verifies_manifest_seeded():
    got = _by_rule(_lint_file(FIXTURES / "seeded_peer_flight.py"),
                   "peer-flight-must-verify-manifest")
    texts = [f.source_line for f in got]
    assert len(got) == 4, texts
    assert any("wait_flights" in t for t in texts)
    assert any("recv_peer_flight" in t for t in texts)
    assert sum("recv_framed" in t for t in texts) == 2
    # verified / grant-gated / raising / pragma'd / framed-layer /
    # supervisor-link twins past the clean_ marker all stay clean
    src = (FIXTURES / "seeded_peer_flight.py").read_text()
    clean_at = src[:src.index("def clean_merge_verified")].count(
        "\n") + 1
    assert all(f.line < clean_at for f in got), [f.line for f in got]


def test_rule_peer_flight_verifies_manifest_scope(tmp_path):
    # the same receive sites outside an exchange/cluster/dcn/shuffle/
    # flight-named file are out of scope; dcn-named files are in
    src = (FIXTURES / "seeded_peer_flight.py").read_text()
    rt = tmp_path / "runtime"
    rt.mkdir()
    plain = rt / "mailbox_like.py"
    plain.write_text(src)
    assert not _by_rule(_lint_file(plain),
                        "peer-flight-must-verify-manifest")
    dcnish = rt / "dcn_like.py"
    dcnish.write_text(src)
    assert _by_rule(_lint_file(dcnish), "peer-flight-must-verify-manifest")


def test_rule_peer_flight_verifies_manifest_shipping_code_complies():
    # the real direct-flight paths must hold their own rule: every peer
    # receive site in runtime/cluster.py, runtime/exchange.py and
    # parallel/dcn.py verifies the manifest/grant before decode
    for rel in (("runtime", "cluster.py"), ("runtime", "exchange.py"),
                ("parallel", "dcn.py")):
        path = REPO / "spark_rapids_jni_tpu" / rel[0] / rel[1]
        assert not _by_rule(_lint_file(path),
                            "peer-flight-must-verify-manifest"), rel


def test_every_rule_has_a_seeded_fixture():
    """The acceptance invariant: all twenty-three per-file rules
    demonstrably fire (the three whole-program rules have their own
    coverage test below)."""
    seen = set()
    for f in _lint_file(FIXTURES / "seeded_fleet_worker_exit.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_host_transfer_device.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_fallback_device.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_python_branch.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_sentinel.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_regex_nul_device.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_bitmask.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_dispatch_device.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_pipeline_stage.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_fusion_region.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_resilience_swallow.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_server_telemetry.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_reservation_memory.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_span_scope.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_payload_memory.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_resultcache_key.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_compress_memory.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_cluster_placement.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_rtfilter_decision.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_exchange_overflow.py"):
        seen.add(f.rule)
    for f in _lint_file(FIXTURES / "seeded_peer_flight.py"):
        seen.add(f.rule)
    ops = Path(__file__).parent / "tpulint_fixtures"  # dtype needs ops/
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        d = Path(td) / "ops"
        d.mkdir()
        shutil.copy(ops / "seeded_dtype_width.py", d / "w.py")
        for f in _lint_file(d / "w.py"):
            seen.add(f.rule)
    assert RULE_NAMES <= seen, RULE_NAMES - seen


# ---------------------------------------------------------------------------
# suppression: pragmas and baseline
# ---------------------------------------------------------------------------

_VIOLATION = (
    "import numpy as np\n"
    "import jax.numpy as jnp\n"
    "def f(keys, valid):\n"
    "    s = np.iinfo(np.int64).max{pragma}\n"
    "    return jnp.where(valid, keys, s)\n"
)


def test_pragma_on_line_suppresses():
    src = _VIOLATION.format(pragma="  # tpulint: disable=sentinel-safety")
    assert not lint_source(src, "x.py")


def test_pragma_comment_line_above_suppresses():
    src = _VIOLATION.format(pragma="")
    lines = src.splitlines()
    lines.insert(3, "    # tpulint: disable=sentinel-safety")
    assert not lint_source("\n".join(lines) + "\n", "x.py")


def test_pragma_disable_all_and_multi_rule():
    assert not lint_source(
        _VIOLATION.format(pragma="  # tpulint: disable=all"), "x.py")
    assert not lint_source(
        _VIOLATION.format(
            pragma="  # tpulint: disable=bitmask-via-helpers,"
                   "sentinel-safety"), "x.py")


def test_pragma_for_other_rule_does_not_suppress():
    src = _VIOLATION.format(
        pragma="  # tpulint: disable=bitmask-via-helpers")
    got = lint_source(src, "x.py")
    assert [f.rule for f in got] == ["sentinel-safety"]


def test_baseline_roundtrip_and_counting(tmp_path):
    src = _VIOLATION.format(pragma="")
    findings = lint_source(src, tmp_path / "x.py")
    assert len(findings) == 1
    bl_path = tmp_path / "baseline.txt"
    write_baseline(findings, bl_path)
    baseline = load_baseline(bl_path)
    new, old = apply_baseline(findings, baseline)
    assert not new and len(old) == 1
    # one baseline entry absorbs exactly ONE occurrence: a second
    # identical violation is a new finding
    doubled = findings + findings
    new, old = apply_baseline(doubled, baseline)
    assert len(new) == 1 and len(old) == 1


def test_baseline_key_is_content_addressed(tmp_path):
    f = Finding("p.py", 10, 0, "sentinel-safety", "msg",
                "s = np.iinfo(np.int64).max")
    g = f._replace(line=99)  # line drift must not invalidate the key
    assert baseline_key(f) == baseline_key(g)


def test_parse_error_is_a_finding(tmp_path):
    got = lint_source("def broken(:\n", tmp_path / "bad.py")
    assert [f.rule for f in got] == ["parse-error"]


# ---------------------------------------------------------------------------
# whole-tree gate (what ci/lint.sh enforces)
# ---------------------------------------------------------------------------

_TREE = ["spark_rapids_jni_tpu", "tools"]


def test_package_tree_is_clean_via_library():
    findings = lint_paths([REPO / p for p in _TREE])
    new, _ = apply_baseline(findings, load_baseline())
    assert not new, "\n".join(
        f"{f.path}:{f.line}: {f.rule}: {f.source_line}" for f in new)


def test_cli_exits_zero_on_package():
    out = subprocess.run(
        [sys.executable, "-m", "tools.tpulint"] + _TREE,
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "clean" in out.stdout


def test_cli_exits_one_on_seeded_fixture():
    out = subprocess.run(
        [sys.executable, "-m", "tools.tpulint",
         "tests/tpulint_fixtures/seeded_bitmask.py"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "bitmask-via-helpers" in out.stdout


def test_cli_list_rules_names_all_rules():
    from tools.tpulint.concurrency import PROGRAM_RULE_NAMES as _PRN
    out = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "--list-rules"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0
    for name in RULE_NAMES | _PRN:
        assert name in out.stdout


def test_cli_write_baseline_then_clean(tmp_path):
    fixture = REPO / "tests/tpulint_fixtures/seeded_bitmask.py"
    bl = tmp_path / "bl.txt"
    wrote = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "--write-baseline",
         "--baseline", str(bl), str(fixture)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert wrote.returncode == 0, wrote.stdout + wrote.stderr
    ran = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "--baseline", str(bl),
         str(fixture)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert ran.returncode == 0, ran.stdout + ran.stderr
    assert "baselined" in ran.stdout


def test_cli_usage_error_without_paths():
    out = subprocess.run(
        [sys.executable, "-m", "tools.tpulint"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 2


# ---------------------------------------------------------------------------
# whole-program concurrency rules (tools/tpulint/flows.py + concurrency.py)
# ---------------------------------------------------------------------------

import json  # noqa: E402

from tools.tpulint.concurrency import (  # noqa: E402
    PROGRAM_RULE_NAMES,
)

PKG_CONCURRENCY = FIXTURES / "pkg_concurrency"


def _by_program_rule(findings, rule):
    assert rule in PROGRAM_RULE_NAMES, rule
    return [f for f in findings if f.rule == rule]


def _clean_marker(path: Path, marker: str) -> int:
    src = path.read_text()
    return src[:src.index(marker)].count("\n") + 1


def test_rule_lock_order_cycle_seeded():
    got = _by_program_rule(
        lint_paths([FIXTURES / "seeded_lock_order.py"]),
        "lock-order-cycle")
    assert len(got) == 1, got
    assert "_alock" in got[0].message and "_block" in got[0].message
    # the order-consistent CleanLedger must NOT contribute a cycle
    clean_at = _clean_marker(FIXTURES / "seeded_lock_order.py",
                             "class CleanLedger")
    assert got[0].line < clean_at


def test_rule_blocking_under_lock_seeded():
    got = _by_program_rule(
        lint_paths([FIXTURES / "seeded_blocking_under_lock.py"]),
        "blocking-call-under-lock")
    assert len(got) == 2, got
    assert any("condition-wait" in f.message for f in got)
    assert any("socket" in f.message for f in got)
    # wait on the lock being waited on, and recv with no lock, are clean
    clean_at = _clean_marker(FIXTURES / "seeded_blocking_under_lock.py",
                             "def clean_park")
    assert all(f.line < clean_at for f in got)


def test_rule_unguarded_write_seeded():
    got = _by_program_rule(
        lint_paths([FIXTURES / "seeded_unguarded_write.py"]),
        "unguarded-shared-write")
    assert len(got) == 1, got
    assert "count" in got[0].message
    assert "self.count = 0" in got[0].source_line
    clean_at = _clean_marker(FIXTURES / "seeded_unguarded_write.py",
                             "class CleanMeter")
    assert got[0].line < clean_at


def test_every_program_rule_has_a_seeded_fixture():
    """The acceptance invariant: all three whole-program rules
    demonstrably fire from their seeded fixtures."""
    seen = set()
    for name in ("seeded_lock_order.py", "seeded_blocking_under_lock.py",
                 "seeded_unguarded_write.py"):
        seen |= {f.rule for f in lint_paths([FIXTURES / name])}
    assert PROGRAM_RULE_NAMES <= seen, PROGRAM_RULE_NAMES - seen


def test_pkg_concurrency_cross_module_cycle():
    """The ABBA cycle only exists across the ledger/vault module
    boundary -- proves call resolution through module imports and
    string annotations."""
    cyc = _by_program_rule(lint_paths([PKG_CONCURRENCY]),
                           "lock-order-cycle")
    assert len(cyc) == 1, cyc
    msg = cyc[0].message
    assert "Ledger._lock" in msg and "Vault._lock" in msg
    assert "ledger.py" in msg and "vault.py" in msg
    # ... and neither file alone is a violation
    assert not _by_program_rule(
        lint_paths([PKG_CONCURRENCY / "vault.py"]), "lock-order-cycle")


def test_pkg_concurrency_foreign_cond_wait_and_clean_twin():
    blk = _by_program_rule(lint_paths([PKG_CONCURRENCY]),
                           "blocking-call-under-lock")
    assert len(blk) == 1, blk
    assert blk[0].path.endswith("waiters.py")
    # clean_nested (consistent nested order) and clean_wait (waits on
    # its own lock) must NOT fire
    clean_at = _clean_marker(PKG_CONCURRENCY / "waiters.py",
                             "def clean_nested")
    assert blk[0].line < clean_at


def test_pkg_concurrency_guard_inference():
    w = _by_program_rule(lint_paths([PKG_CONCURRENCY]),
                         "unguarded-shared-write")
    assert len(w) == 1, w
    assert w[0].path.endswith("gauges.py")
    assert "value" in w[0].message
    # peak's only bare site is a READ: never flagged
    assert not any("peak" in f.message for f in w)


def test_entry_held_inference_charges_locked_helper(tmp_path):
    """A private ``*_locked``-style helper called under the lock at
    every call site inherits the held set (entry-held inference)."""
    src = (
        "import threading\n"
        "class Pool:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._sock = None\n"
        "    def _drain_locked(self):\n"
        "        return self._sock.recv(1024)\n"
        "    def take(self):\n"
        "        with self._lock:\n"
        "            return self._drain_locked()\n"
        "    def flush(self):\n"
        "        with self._lock:\n"
        "            return self._drain_locked()\n"
    )
    t = tmp_path / "pool.py"
    t.write_text(src)
    got = _by_program_rule(lint_paths([t]), "blocking-call-under-lock")
    # the recv inside the helper itself is charged (line 7), not just
    # the call sites -- that requires the inferred entry-held set
    assert any(f.line == 7 for f in got), got


def test_uncalled_public_function_gets_no_entry_held(tmp_path):
    """Entry-held inference must never assume a caller's lock for a
    public method -- same shape as above but public name, no finding
    inside the helper body."""
    src = (
        "import threading\n"
        "class Pool:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._sock = None\n"
        "    def drain(self):\n"
        "        return self._sock.recv(1024)\n"
    )
    t = tmp_path / "pool.py"
    t.write_text(src)
    assert not _by_program_rule(lint_paths([t]),
                                "blocking-call-under-lock")


def test_program_rule_pragma_suppresses(tmp_path):
    src = (FIXTURES / "seeded_unguarded_write.py").read_text()
    src = src.replace(
        "self.count = 0                 # VIOLATION: bare write, "
        "guarded elsewhere",
        "self.count = 0  # tpulint: disable=unguarded-shared-write")
    t = tmp_path / "m.py"
    t.write_text(src)
    assert not _by_program_rule(lint_paths([t]),
                                "unguarded-shared-write")


def test_condition_alias_is_one_lock(tmp_path):
    """``Condition(self._lock)`` must canonicalize to the wrapped lock:
    waiting on the condition while holding the SAME lock via either
    name is clean."""
    src = (
        "import threading\n"
        "class Gate:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.RLock()\n"
        "        self._cond = threading.Condition(self._lock)\n"
        "    def park(self):\n"
        "        with self._lock:\n"
        "            self._cond.wait(0.1)\n"
    )
    t = tmp_path / "gate.py"
    t.write_text(src)
    assert not _by_program_rule(lint_paths([t]),
                                "blocking-call-under-lock")


# ---------------------------------------------------------------------------
# CLI: --format json and --lock-graph
# ---------------------------------------------------------------------------


def test_cli_format_json_structure_and_exit():
    out = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "--format", "json",
         "tests/tpulint_fixtures/seeded_lock_order.py"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["counts"]["new"] >= 1
    keys = {"rule", "path", "line", "col", "message", "source_line",
            "status"}
    assert all(keys <= set(r) for r in doc["findings"])
    assert any(r["rule"] == "lock-order-cycle" and r["status"] == "new"
               for r in doc["findings"])


def test_cli_format_json_reports_pragma_status(tmp_path):
    src = (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "def f(keys, valid):\n"
        "    s = np.iinfo(np.int64).max"
        "  # tpulint: disable=sentinel-safety\n"
        "    return jnp.where(valid, keys, s)\n"
    )
    t = tmp_path / "x.py"
    t.write_text(src)
    out = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "--format", "json",
         "--no-baseline", str(t)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["counts"]["new"] == 0
    assert doc["counts"]["pragma"] == 1
    assert any(r["status"] == "pragma"
               and r["rule"] == "sentinel-safety"
               for r in doc["findings"])


def test_cli_lock_graph_acyclic_on_live_package():
    out = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "--lock-graph",
         "spark_rapids_jni_tpu"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "acyclic" in out.stdout


def test_cli_lock_graph_json_flags_fixture_cycle():
    out = subprocess.run(
        [sys.executable, "-m", "tools.tpulint", "--lock-graph",
         "--format", "json", "tests/tpulint_fixtures/pkg_concurrency"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert not doc["acyclic"]
    assert doc["cycles"]
    assert any("Ledger" in n for cyc in doc["cycles"] for n in cyc)
