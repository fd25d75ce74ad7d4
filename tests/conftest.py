"""Test harness configuration.

Unit tests run on a virtual 8-device CPU mesh (the driver validates the real
multi-chip path separately via __graft_entry__.dryrun_multichip). Env must be
set before jax initializes its backends, hence at conftest import time.

Mirrors the reference's test policy (SURVEY.md section 4): round-trip /
golden-equality against a host oracle; device-conditional features gated by
markers, not mocks.
"""

import os
import shutil
import tempfile

# The persistent compile cache of a pytest run lives in a temporary directory
# of its own, never in the checkout's .jax_cache (which the chip tool would
# then copy). JAX reads both variables itself, before it is imported, and
# every worker process a test boots inherits them; a threshold of 0 persists
# the sub-second CPU compiles too, so the many tests (and workers) that
# compile the same small plan after a dispatch.clear() pay for it once a
# session. The cache tests (tests/test_chip_smoke.py) run child processes
# with their own environment.
_SESSION_CACHE = tempfile.mkdtemp(prefix="spark_rapids_jni_tpu_pytest_cache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _SESSION_CACHE
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

from spark_rapids_jni_tpu.utils.platform import force_cpu_platform  # noqa: E402

force_cpu_platform(n_virtual_devices=8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Premerge tier manifest (VERDICT r4 weak #4 / item 9): the fast tier had
# grown to ~23 min because the heaviest oracle sweeps carried no marker.
# Every test below measured >=14 s on the reference box (pytest
# --durations, 2026-07-31 run; the 10 window/groupby oracle sweeps alone
# were ~20 min). They are auto-marked `medium`: premerge deselects them
# (-m "not slow and not medium"), the nightly still runs everything —
# coverage moved between tiers, never deleted. Keep this list in sync
# with new slow oracle sweeps; entries are nodeids without param ids.
# ---------------------------------------------------------------------------
_MEDIUM_TIER = {
    "tests/test_cast_strings.py::test_string_to_date_vs_python_oracle",
    "tests/test_decimal128_ops.py::test_decimal128_minmax_vs_python",
    "tests/test_json_device.py::test_device_engine_matches_native_randomized",
    "tests/test_lists.py::test_string_list_pipeline_end_to_end",
    "tests/test_native_ops.py::test_get_json_object_missing_and_oob",
    "tests/test_ops.py::test_groupby_and_q1_compile_scatter_free",
    "tests/test_ops.py::test_groupby_covar_corr_vs_numpy",
    "tests/test_ops.py::test_groupby_float_small_group_after_large_group",
    "tests/test_ops.py::test_groupby_small_m_exact_fit_and_overflow",
    "tests/test_ops.py::test_groupby_small_m_matches_default_path",
    "tests/test_ops.py::test_groupby_sum_count_vs_numpy",
    "tests/test_ops.py::test_groupby_var_pop_std_pop_vs_numpy",
    "tests/test_ops.py::test_groupby_var_std_vs_numpy",
    "tests/test_parallel.py::test_distributed_groupby_covar_corr",
    "tests/test_parallel.py::test_tpch_q1_distributed_matches_oracle",
    "tests/test_parallel.py::test_tpch_q1_distributed_matches_single_device",
    "tests/test_parallel_strings.py::test_tpch_q1_distributed_string_flags",
    "tests/test_regex_device.py::test_random_pattern_fuzz_vs_host",
    "tests/test_strings.py::TestStringGroupBy::test_max_groups_overflow_and_auto",
    "tests/test_strings.py::test_like_multibyte_vs_regex_oracle",
    "tests/test_strings.py::test_like_underscore_multibyte_utf8_char_semantics",
    "tests/test_strings.py::test_like_vs_regex_oracle",
    "tests/test_strings_fns.py::test_split_literal_vs_python",
    "tests/test_table_ops.py::test_except_intersect_vs_python",
    "tests/test_tpcds.py::test_q64_base_year_anchors_dates",
    "tests/test_tpcds.py::test_q64_matches_oracle",
    "tests/test_tpcds.py::test_q64_sorted_by_count_desc",
    "tests/test_tpcds.py::test_q72_distributed_matches_oracle",
    "tests/test_tpcds.py::test_q72_matches_oracle",
    "tests/test_tpcds.py::test_q72_year_filter_changes_result",
    "tests/test_tpch.py::test_q1_groups_sorted_first",
    "tests/test_tpch.py::test_q1_matches_numpy_oracle",
    "tests/test_tpch.py::test_q1_planned_checked_replans_on_domain_miss",
    "tests/test_tpch.py::test_q1_planned_matches_oracle_and_is_sort_free",
    "tests/test_tpch.py::test_tpch_q12_vs_numpy",
    "tests/test_tpch.py::test_tpch_q14_vs_numpy",
    "tests/test_tpch.py::test_tpch_q17_vs_numpy",
    "tests/test_tpch.py::test_tpch_q19_vs_numpy",
    "tests/test_tpch.py::test_tpch_q1_checked_rejects_out_of_contract_key_domain",
    "tests/test_tpch.py::test_tpch_q4_vs_numpy",
    "tests/test_window.py::test_first_last_nth_value",
    "tests/test_window.py::test_ntile_percent_rank_cume_dist",
    "tests/test_window.py::test_range_frames_vs_oracle",
    "tests/test_window.py::test_rolling_frames_vs_oracle",
    "tests/test_window.py::test_rolling_min_max_vs_oracle",
    "tests/test_window.py::test_rolling_sum_decimal128_exact",
    "tests/test_window.py::test_rolling_var_std_vs_oracle",
    "tests/test_window.py::test_window_functions_vs_oracle",
    "tests/test_window.py::test_window_string_lag_and_float_running_sum",
    # round-5 additions measured locally over the same threshold
    "tests/test_outofcore.py::test_q1_outofcore_matches_oracle_under_budget",
    "tests/test_planner.py::test_q12_planned_matches_oracle",
    "tests/test_planner.py::test_q4_planned_matches_oracle",
    # second round-5 durations pass (>=9.5 s): 8-device shard_map
    # compiles and oracle sweeps
    "tests/test_cast_strings.py::test_date_roundtrip_through_strings",
    "tests/test_cast_strings.py::test_string_to_timestamp_vs_python_oracle",
    "tests/test_decimal128_ops.py::test_decimal128_sum_small_m_path_matches",
    "tests/test_distributed_bounded.py::test_domain_miss_propagates_from_one_shard",
    "tests/test_distributed_bounded.py::test_groups_absent_everywhere_not_present",
    "tests/test_distributed_bounded.py::test_nondivisible_rows_no_phantom_null_group",
    "tests/test_distributed_bounded.py::test_output_replicated_not_sharded",
    "tests/test_distributed_bounded.py::test_scalar_keys_match_oracle",
    "tests/test_distributed_bounded.py::test_string_keys_under_shard_map",
    "tests/test_distributed_bounded.py::test_q72_planned_distributed_zero_shuffle_matches_oracle",
    "tests/test_distributed_bounded.py::test_q3_planned_distributed_broadcast_plan_matches_oracle",
    "tests/test_json_device.py::test_device_engine_adversarial_structurals",
    "tests/test_ops.py::test_groupby_first_last_vs_oracle",
    "tests/test_outofcore.py::test_run_chunked_aggregate_with_prefetch_matches",
    "tests/test_planner.py::test_q19_planned_matches_oracle_and_sort_free",
    "tests/test_planner.py::test_q64_planned_join_elimination_matches_oracle",
    "tests/test_strings.py::TestStringMinMax::test_min_max_matches_oracle",
    "tests/test_outofcore.py::test_q3_outofcore_join_side_matches_oracle",
    "tests/test_distributed_bounded.py::test_outofcore_times_distributed_composition",
    "tests/test_distributed_bounded.py::test_q5_distributed_zero_shuffle_matches_single_and_oracle",
}


def pytest_collection_modifyitems(config, items):
    matched = set()
    collected_files = set()
    for item in items:
        base = item.nodeid.split("[")[0]
        collected_files.add(base.split("::")[0])
        if base in _MEDIUM_TIER:
            item.add_marker(pytest.mark.medium)
            matched.add(base)
    # drift guard: a manifest entry whose FILE was collected but whose
    # test no longer exists means a renamed/deleted heavy test would
    # silently rejoin the premerge fast tier — fail loud instead.
    # (Entries for files outside this collection are fine: subset runs
    # like `pytest tests/test_ops.py` must not trip the guard; nodeid-
    # or -k-narrowed invocations skip it entirely — they collect a
    # deliberate subset of a file.)
    narrowed = (any("::" in a for a in config.args)
                or bool(getattr(config.option, "keyword", "")))
    stale = [] if narrowed else [
        e for e in _MEDIUM_TIER
        if e.split("::")[0] in collected_files and e not in matched]
    if stale:
        raise pytest.UsageError(
            "medium-tier manifest entries match no collected test "
            f"(renamed? update tests/conftest.py): {sorted(stale)}")


def pytest_sessionfinish(session, exitstatus):
    shutil.rmtree(_SESSION_CACHE, ignore_errors=True)


@pytest.fixture(autouse=True)
def _fresh_learned_stores():
    """The learned admission estimates and runtime-filter selectivities
    persist beside the compile cache (utils/config.cache_dir()): every test
    starts without what earlier tests learned."""
    for name in ("learned_estimates.json", "learned_selectivity.json"):
        for path in (name, name + ".lock"):
            try:
                os.unlink(os.path.join(_SESSION_CACHE, path))
            except FileNotFoundError:
                pass
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(42)
