"""Observability under the sharded mesh path (8 virtual CPU devices):
per-shard label sets stay distinct, cross-registry merge (the
multi-host aggregation primitive) is loss-free, health rules evaluate
over merged series, and an obs-enabled parallelism=8 chapter-3 job
reports the same record counts as single-chip plus the sharded-only
gauges and end-to-end latency markers."""

import jax
import pytest

from tpustream import StreamExecutionEnvironment, Time, TimeCharacteristic
from tpustream.config import ObsConfig, StreamConfig
from tpustream.jobs.chapter3_bandwidth_eventtime import build as build_et
from tpustream.obs import (
    AlertRule,
    HealthEngine,
    JobObs,
    MetricsRegistry,
)
from tpustream.runtime.sources import ReplaySource

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-virtual-device CPU mesh"
)


# ---------------------------------------------------------------------------
# per-shard labeling + registry merge (no device work)
# ---------------------------------------------------------------------------


def test_per_shard_operator_labels_distinct():
    job = JobObs(ObsConfig(enabled=True), job_name="j")
    op = job.operator("window")
    shards = [op.shard(i) for i in range(8)]
    for i, sh in enumerate(shards):
        sh.records_in.inc(10 + i)
    series = {
        s.labels["shard"]: s
        for s in job.registry.series()
        if s.name == "operator_records_in" and "shard" in s.labels
    }
    assert sorted(series) == [str(i) for i in range(8)]  # all distinct
    for i in range(8):
        assert series[str(i)].value == 10 + i  # no cross-shard bleed
        assert series[str(i)].labels["operator"] == "window"


def test_registry_merge_across_shards_lossless_and_health_over_merged():
    """The multi-host shape: each shard keeps its own registry; the
    coordinator merges them and evaluates health over the union."""
    regs = []
    for i in range(8):
        r = MetricsRegistry()
        g = r.group(job="j", operator="window", shard=str(i))
        g.counter("operator_records_in").inc(100 + i)
        g.histogram("operator_e2e_latency_ms").observe_many(
            [float(i + 1), float(i + 2)]
        )
        regs.append(r)

    merged = MetricsRegistry()
    for r in regs:
        merged.merge(r)

    series = list(merged.series())
    counters = [s for s in series if s.name == "operator_records_in"]
    assert len(counters) == 8  # one per shard, none collapsed
    assert sum(s.value for s in counters) == sum(100 + i for i in range(8))
    hists = [s for s in series if s.name == "operator_e2e_latency_ms"]
    assert sum(h.count for h in hists) == 16  # exact under merge
    assert sum(h.sum for h in hists) == sum(
        (i + 1) + (i + 2) for i in range(8)
    )

    # a single rule set sees every shard's series; agg=max picks the
    # worst shard, the label filter pins one shard
    snap = merged.snapshot()["series"]
    engine = HealthEngine([
        AlertRule(name="hot_shard", metric="operator_records_in",
                  op=">", value=106, agg="max", severity="crit"),
        AlertRule(name="shard0", metric="operator_records_in",
                  op=">", value=100, labels={"shard": "0"},
                  severity="warn"),
    ])
    state = engine.evaluate(snap, now_s=1.0)
    by_name = {r["rule"]: r for r in state["rules"]}
    assert by_name["hot_shard"]["level"] == "crit"   # shard 7: 107 > 106
    assert by_name["hot_shard"]["value"] == 107
    assert by_name["shard0"]["level"] == "ok"        # shard 0: 100, not > 100


# ---------------------------------------------------------------------------
# e2e: obs-enabled sharded job vs single-chip
# ---------------------------------------------------------------------------

LINES = [
    f"2019-08-28T10:{i // 20:02d}:{(i * 7) % 60:02d} "
    f"www.ch{i % 16}.com {100 + (i % 13) * 10}"
    for i in range(200)
]


def _run(parallelism):
    cfg = StreamConfig(
        parallelism=parallelism,
        batch_size=40,
        key_capacity=64,
        print_parallelism=1,
        obs=ObsConfig(
            enabled=True,
            latency_marker_interval_ms=1e-6,
            health_rules=(
                AlertRule(name="lag_crit", metric="watermark_lag_ms",
                          op=">", value=30_000, severity="crit"),
            ),
        ),
    )
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    h = build_et(env, env.add_source(ReplaySource(LINES))).collect()
    env.execute("obs-sharded")
    return env.metrics, sorted((t.f0, round(t.f1, 12)) for t in h.items)


def test_hbm_state_bytes_shard_split_sums_to_single_chip_total():
    """State-memory accounting is consistent across the mesh: the state
    pytree is global, so the p=8 total equals the single-chip total
    byte-for-byte, the per-shard attribution series sum back to it
    exactly, and the shard label set / exchange staging gauge exist
    only on the mesh."""

    def _hbm(metrics):
        total, shards, exchange = None, {}, None
        for s in metrics.obs_snapshot()["metrics"]["series"]:
            if s["name"] == "operator_hbm_state_bytes":
                if "shard" in s["labels"]:
                    shards[s["labels"]["shard"]] = s["value"]
                else:
                    total = s["value"]
            elif s["name"] == "operator_exchange_buffer_bytes":
                exchange = s["value"]
        return total, shards, exchange

    m1, out1 = _run(parallelism=1)
    m8, out8 = _run(parallelism=8)
    assert out1 == out8

    tot1, shards1, ex1 = _hbm(m1)
    tot8, shards8, ex8 = _hbm(m8)
    assert tot1 > 0
    assert shards1 == {} and ex1 is None  # single chip: no mesh series
    assert tot8 == tot1
    assert sorted(shards8) == [str(i) for i in range(8)]
    assert sum(shards8.values()) == tot8
    assert ex8 > 0


def test_sharded_job_obs_matches_single_chip():
    m1, out1 = _run(parallelism=1)
    m8, out8 = _run(parallelism=8)
    assert out1 == out8  # obs never changes results

    s1 = {(s["name"], s["labels"].get("operator")): s
          for s in m1.obs_snapshot()["metrics"]["series"]}
    s8 = {(s["name"], s["labels"].get("operator")): s
          for s in m8.obs_snapshot()["metrics"]["series"]}

    # same record accounting either way
    for key in (("records_in", None), ("operator_records_in", "window")):
        assert s8[key]["value"] == s1[key]["value"] == len(LINES)

    # sharded-only surface: the exchange-capacity gauge
    assert ("operator_exchange_capacity_rows", "window") in s8
    assert s8[("operator_exchange_capacity_rows", "window")]["value"] > 0
    assert ("operator_exchange_capacity_rows", "window") not in s1

    # markers survive the sharded path end to end, none lost
    for s in (s1, s8):
        emitted = s[("latency_markers_emitted", None)]["value"]
        assert emitted >= 4  # 200 lines / 40-row batches = 5 polls
        h = s[("operator_sink0_e2e_latency_ms", "window")]
        assert h["value"]["count"] == emitted
        assert h["value"]["p50"] > 0

    # the health engine saw the merged/sharded series identically
    for m in (m1, m8):
        health = m.obs_snapshot()["health"]
        assert health["rules"][0]["rule"] == "lag_crit"
        assert health["rules"][0]["level"] == "crit"  # 60 s bounded delay


# ---------------------------------------------------------------------------
# time-series merge across p=8 shard registries (satellite: windowed
# queries over the merged history match a single-chip oracle)
# ---------------------------------------------------------------------------


def _pin(reg, clk):
    """Put a registry on the shared fake timeline (wall == perf epoch),
    so cross-registry history merges line up deterministically."""
    reg.now = lambda: clk[0]
    reg._epoch_wall = 0.0
    reg._epoch_perf = 0.0
    return reg


def test_timeseries_merge_p8_matches_single_chip_oracle():
    """Eight shard registries on one shared timeline, each counting at
    its own rate and observing its own latencies, merged into one
    coordinator registry: the merged ``rate()`` equals the sum of the
    per-shard rates and the merged ``quantile()`` equals a single
    registry that saw every observation — lossless, not approximate."""
    clk = [0.0]
    shards = []
    oracle = _pin(MetricsRegistry(), clk)
    oc = oracle.group(job="j").counter("records_in")
    oh = oracle.group(job="j").histogram("e2e_latency_ms")
    for i in range(8):
        r = _pin(MetricsRegistry(), clk)
        shards.append(r)
    # mint the shard instruments at t=0 so every zero-anchor shares the
    # timeline origin
    scs = [r.group(job="j").counter("records_in") for r in shards]
    shs = [r.group(job="j").histogram("e2e_latency_ms") for r in shards]
    for t in range(1, 11):
        clk[0] = float(t)
        for i in range(8):
            scs[i].inc(i + 1)            # shard i ingests (i+1) rows/s
            oc.inc(i + 1)
            lat = float(10 * (i + 1) + t % 3)
            shs[i].observe(lat)
            oh.observe(lat)

    merged = _pin(MetricsRegistry(), clk)
    for r in shards:
        merged.merge(r)

    mc = merged.find("records_in", {"job": "j"})
    mh = merged.find("e2e_latency_ms", {"job": "j"})
    # lossless totals
    assert mc.value == oc.value == 10 * sum(range(1, 9))
    assert mh.count == oh.count == 80
    assert mh.sum == pytest.approx(oh.sum)
    # windowed rate over the merged cumulative history == sum of the
    # per-shard windowed rates == the oracle's rate
    per_shard = sum(c.history.rate(9.0) for c in scs)
    assert mc.history.rate(9.0) == pytest.approx(per_shard)
    assert mc.history.rate(9.0) == pytest.approx(oc.history.rate(9.0))
    assert mc.history.rate(9.0) == pytest.approx(float(sum(range(1, 9))))
    # windowed quantiles over the merged sample history == single-chip
    for q in (0.1, 0.5, 0.9, 0.99):
        assert mh.history.quantile(q, 9.0) == pytest.approx(
            oh.history.quantile(q, 9.0)
        )
    assert mh.history.mean(9.0) == pytest.approx(oh.history.mean(9.0))


def test_tenant_series_merge_p8_matches_single_chip_oracle():
    """Per-tenant latency series across 8 shard registries, merged into
    one coordinator registry: each tenant's merged histogram is lossless
    (windowed quantiles match an oracle registry that saw every one of
    that tenant's observations), and tenant SLO rules evaluated over the
    MERGED series attribute the breach to the noisy tenant only."""
    clk = [0.0]
    tenants = ["acme", "globex", "initech"]
    oracle = _pin(MetricsRegistry(), clk)
    ohs = {
        t: oracle.group(job="j", tenant=t).histogram("tenant_e2e_latency_ms")
        for t in tenants
    }
    shards = [_pin(MetricsRegistry(), clk) for _ in range(8)]
    shs = {
        (i, t): shards[i].group(job="j", tenant=t).histogram(
            "tenant_e2e_latency_ms"
        )
        for i in range(8)
        for t in tenants
    }
    for tick in range(1, 11):
        clk[0] = float(tick)
        for i in range(8):
            for tenant in tenants:
                # acme is the noisy tenant: 10x everyone's latency
                scale = 10.0 if tenant == "acme" else 1.0
                lat = scale * (i + 1) + tick % 3
                shs[(i, tenant)].observe(lat)
                ohs[tenant].observe(lat)

    merged = _pin(MetricsRegistry(), clk)
    for r in shards:
        merged.merge(r)

    for tenant in tenants:
        mh = merged.find(
            "tenant_e2e_latency_ms", {"job": "j", "tenant": tenant}
        )
        oh = ohs[tenant]
        assert mh.count == oh.count == 80
        assert mh.sum == pytest.approx(oh.sum)
        for q in (0.5, 0.9, 0.99):
            assert mh.percentile(q) == pytest.approx(oh.percentile(q))
            assert mh.history.quantile(q, 9.0) == pytest.approx(
                oh.history.quantile(q, 9.0)
            )

    # per-tenant SLO rules over the merged union: the label filter keeps
    # each rule on its own tenant's series, so only acme trips
    from tpustream.obs.slo import TenantSLO, compile_tenant_slo

    engine = HealthEngine([
        r
        for t in tenants
        for r in compile_tenant_slo(
            t, TenantSLO(p99_ms=20.0, budget_window_s=60.0)
        )
    ])
    state = engine.evaluate(merged.snapshot()["series"], now_s=clk[0])
    by = {r["rule"]: r for r in state["rules"]}
    assert by["slo_p99[acme]"]["level"] == "crit"
    assert by["slo_p99[acme]"]["labels"] == {"tenant": "acme"}
    assert by["slo_p99[globex]"]["level"] == "ok"
    assert by["slo_p99[initech]"]["level"] == "ok"
