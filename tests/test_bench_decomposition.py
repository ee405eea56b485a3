"""Tier-1 bench smoke: the full-path decomposition and H2D-bandwidth
phases run in tiny mode on CPU, so stage-timing regressions (a stage
key disappearing, the pipelined pass deadlocking, the bandwidth probe
reverting to its RTT-corrupted form) are caught without the full bench.
"""

import importlib.util
import json
import os

import pytest


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")
    spec = importlib.util.spec_from_file_location("bench_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_decompose_full_path_tiny_mode(bench):
    d = bench.decompose_full_path(n_batches=2, bl=256, nkey=1024)
    s = d["stages_ms"]
    for key in (
        "parse_intern_ms", "pack_ms", "h2d_step_fetch_ms",
        "count_fetch_rtt_ms", "batch_total_sync_ms",
    ):
        assert key in s and s[key] >= 0, key
    assert d["rows_per_batch"] == 256
    assert d["sync_rows_per_s"] > 0
    assert d["binding_stage"] in ("parse_intern_ms", "h2d_step_fetch_ms")
    # the packed wire format must only ever shrink a row
    assert 0 < d["bytes_per_row_packed"] <= d["bytes_per_row_raw"]
    assert d["wire_bytes_per_row"] == d["bytes_per_row_packed"]
    # the pipelined pass ran and drained (deadlock here = no number)
    assert d["pipelined_ms_per_batch"] > 0
    assert d["pipelined_rows_per_s"] > 0


def test_ingest_lane_sweep_tiny_mode(bench):
    """Phase I2 in tiny mode: the lane sweep runs end to end, every
    lane count reports a positive rate over the full line budget, and
    the merged column digests are byte-identical to the 1-lane run —
    the whole point of the sharded-ingestion contract."""
    d = bench.ingest_lane_sweep(
        lane_counts=(1, 2), nbuf=4, warm=1, bl=1024, nkey=1 << 12
    )
    assert [e["lanes"] for e in d["results"]] == [1, 2]
    base = d["results"][0]["sha256"]
    for e in d["results"]:
        assert e["lines_per_s"] > 0
        assert e["n_lines"] == d["lines_per_run"]
        assert e["sha256"] == base
        assert e["byte_identical_to_1_lane"]


def test_measure_h2d_reports_positive_bandwidth(bench):
    mb_s = bench.measure_h2d()
    assert mb_s > 0


def test_multitenancy_probe_tiny_mode(bench):
    """Phase T in tiny mode: two fleet sizes, each through one compiled
    program with a hot per-tenant rule write — throughput/cost keys
    present, oracle output intact, zero config_change recompiles."""
    d = bench.multitenancy_probe(
        tenant_counts=(1, 4), records_per_tenant=8, batch_size=16
    )
    assert [e["tenants"] for e in d["sweep"]] == [1, 4]
    for e in d["sweep"]:
        assert e["events_per_s"] > 0 and e["ms_per_batch"] > 0
        assert e["config_change_recompiles"] == 0
        assert e["updated_tenant_matches_oracle"]
    assert d["zero_config_change_recompiles"]
    assert d["all_outputs_match"]


def test_tenant_slo_probe_tiny_mode(bench):
    """Phase T SLO leg in tiny mode: an 8-tenant fleet with one tenant
    flooding 5x its quota — the flooder's error SLO goes CRIT with a
    burned budget, the other 7 tenants stay OK, and the /tenants.json
    view assembles."""
    d = bench.tenant_slo_probe(
        tenants=8, records_per_tenant=4, flood_factor=5, batch_size=16
    )
    assert d["tenants"] == 8
    assert d["events_per_s"] > 0
    # 20 offered, 4 admitted: 16/20 diverted
    assert d["flooder_error_rate"] == pytest.approx(0.8)
    assert d["flooder_level"] == "crit"
    assert d["flooder_budget_burn"] == pytest.approx(1.0)
    assert d["others_ok"] == 7
    assert d["tenants_json_scrape_ms"] >= 0


def test_ledger_overhead_probe_tiny_mode(bench):
    """Phase O3 in tiny mode: the ledger on/off legs both run, the
    collected rows stay byte-identical (the ledger never touches a
    record), every evaluated invariant's residual is exactly zero, no
    violation latched, and both sinks carry a digest anchor."""
    d = bench.ledger_overhead_probe()
    assert d["output_identical"]
    assert d["sink_digest_base"] == d["sink_digest_ledger"]
    assert d["edges_evaluated"] >= 3  # source, sink0, contents edges
    assert d["all_residuals_zero"]
    assert all(r == 0 for r in d["residuals"].values() if r is not None)
    assert d["violations"] == 0
    assert "sink0" in d["anchors"]
    a = d["anchors"]["sink0"]
    assert a["count"] > 0 and len(a["digest"]) == 64 and a["verifiable"]


def test_checkpoint_overhead_probe_tiny_mode(bench):
    """Phase C2 in tiny mode: both plane postures run at both state
    sizes, the sink output is byte-identical across them (the plane
    never touches results), the incremental leg reuses chunks and ships
    fewer bytes than the sync-full leg, and the comparable top-level
    barrier-stall scalar comes out."""
    d = bench.checkpoint_overhead_probe(sizes=(("small", 16), ("large", 64)))
    assert d["outputs_identical"]
    assert d["barrier_stall_ms"] > 0
    for label in ("small", "large"):
        s = d[label]
        assert s["outputs_identical"]
        sync, inc = s["sync_full"], s["async_incremental"]
        assert sync["snapshots"] > 0 and inc["snapshots"] > 0
        assert sync["barrier_stall_ms_p99"] > 0
        assert inc["barrier_stall_ms_p99"] > 0
        # sync-full rewrites everything every snapshot; the incremental
        # plane reuses stable chunks, so it must ship strictly less
        assert sync["bytes_written"] == sync["bytes_state"]
        assert inc["chunks_reused"] > 0
        assert inc["bytes_written"] < sync["bytes_written"]
        assert s["delta_bytes_ratio"] < 1.0


def test_compare_smoke_same_env(bench, tmp_path):
    """Schema-2 records minted on this host compare cleanly: the env
    fingerprint matches itself, per-phase deltas come out, and the CI
    gate stays green on an improvement."""
    env = bench._resources_module().collect_env_fingerprint().to_dict()
    rec = {
        "bench": "tpu-stream-monitor",
        "bench_schema": bench.BENCH_SCHEMA,
        "env": env,
        "value": 100.0,
        "round_detail": {
            "sync_rows_per_s": 1000.0,
            "ledger": {"overhead_pct": 2.0},
            "checkpointing": {"barrier_stall_ms": 8.0},
        },
    }
    old = tmp_path / "old.json"
    old.write_text(json.dumps(rec))
    new = tmp_path / "new.json"
    new.write_text(
        json.dumps(dict(rec, round_detail={
            "sync_rows_per_s": 1500.0,
            "ledger": {"overhead_pct": 1.0},
            "checkpointing": {"barrier_stall_ms": 4.0},
        }))
    )
    loaded = bench.load_bench_record(str(old))
    assert loaded["error"] is None
    assert loaded["schema"] == bench.BENCH_SCHEMA
    assert loaded["env"]["usable_cores"] >= 1
    cmp = bench.compare_records(loaded, bench.load_bench_record(str(new)))
    assert cmp["comparable"] is True
    assert any(d["phase"] == "sync_rows_per_s" for d in cmp["deltas"])
    # the ledger phase flattens in, and less overhead is an improvement
    assert any(
        d["phase"] == "ledger.overhead_pct" for d in cmp["deltas"]
    )
    assert any(
        d["phase"] == "ledger.overhead_pct" for d in cmp["improvements"]
    )
    # the checkpoint plane's barrier stall flattens in as a _ms metric,
    # so a smaller stall is an improvement, never a regression
    assert any(
        d["phase"] == "checkpointing.barrier_stall_ms"
        for d in cmp["improvements"]
    )
    assert bench.run_compare([str(old), str(new)], gate=True) == 0
