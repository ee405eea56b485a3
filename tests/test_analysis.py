"""Pre-flight plan analyzer (tpustream/analysis, docs/analysis.md).

Contracts pinned here:

* every plan-lint rule has a BROKEN job that produces its exact TSM0xx
  code and a clean job that does not;
* the purity analyzer flags mutable closures, nondeterministic calls,
  device side effects, host callbacks, and dtype-widening maps — and
  stays silent on the pure equivalents;
* ``strict_analysis=True`` raises PlanAnalysisError at submission,
  BEFORE any planning or tracing;
* with obs enabled, findings surface as
  ``analysis_findings_total{code=...}`` counters and flight breadcrumbs;
* ``python -m tpustream.analysis.lint`` exits 0/1/2 correctly and all
  nine chapter jobs self-lint with zero errors.

Everything except the obs-integration test constructs graphs without
executing them — analysis is pure inspection.
"""

import io
import textwrap

import numpy as np
import pytest

from tpustream import (
    CEP,
    OutputTag,
    Pattern,
    PlanAnalysisError,
    StreamExecutionEnvironment,
    Time,
    TimeCharacteristic,
    Tuple3,
)
from tpustream.analysis import (
    CATALOG,
    ERROR,
    INFO,
    WARN,
    analyze,
    analyze_callable,
    check_dtype_widening,
    has_errors,
)
from tpustream.analysis.lint import main as lint_main
from tpustream.api.datastream import KeyedStream
from tpustream.api.graph import Node
from tpustream.config import ObsConfig, StreamConfig
from tpustream.jobs.chapter1_threshold import parse
from tpustream.runtime.sources import ReplaySource
from tpustream.runtime.supervisor import fixed_delay


def codes(findings):
    return [f.code for f in findings]


def make_env(**cfg) -> StreamExecutionEnvironment:
    return StreamExecutionEnvironment(StreamConfig(**cfg))


def good_job(env=None):
    """A clean chapter-2-style windowed job: parse -> key -> window sum."""
    env = env or make_env()
    (
        env.from_collection([])
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .sum(2)
        .print()
    )
    return env


# ---------------------------------------------------------------------------
# plan-lint rules: broken job -> exact code; clean job -> silent
# ---------------------------------------------------------------------------


def test_clean_job_has_no_findings():
    env = good_job()
    findings = env.analyze()
    assert not has_errors(findings)
    assert findings == []


def test_tsm001_stateful_without_key_by():
    env = make_env()
    stream = env.from_collection([]).map(parse)
    # cast past the type surface: a rolling max with NO key_by upstream
    KeyedStream(env, stream.node).max(2).print()
    found = env.analyze()
    assert "TSM001" in codes(found)
    # the targeted ERROR explains the failure; the planner catch-all
    # (TSM014) must NOT pile on
    assert "TSM014" not in codes(found)


def test_tsm002_event_time_window_without_assigner():
    env = make_env()
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    (
        env.from_collection([])
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .sum(2)
        .print()
    )
    found = env.analyze()
    assert "TSM002" in codes(found)
    assert next(f for f in found if f.code == "TSM002").severity == ERROR


def test_tsm003_side_output_tag_collision():
    env = make_env()
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    tag = OutputTag("late")
    text = env.from_collection([]).map(parse)
    for _ in range(2):
        (
            text.key_by(0)
            .time_window(Time.seconds(5))
            .allowed_lateness(Time.seconds(1))
            .side_output_late_data(tag)
            .sum(2)
            .print()
        )
    assert "TSM003" in codes(env.analyze())


def test_tsm004_timeout_tag_without_within():
    env = make_env()
    pattern = Pattern.begin("a").where(lambda r: r.f2 > 0).times(2)
    keyed = env.from_collection([]).map(parse).key_by(0)
    alerts = CEP.pattern(keyed, pattern).select(
        lambda m: m["a"][0], timeout_tag=OutputTag("to")
    )
    alerts.print()
    alerts.get_side_output(OutputTag("to")).print()
    assert "TSM004" in codes(env.analyze())


def test_tsm004_lateness_on_processing_time():
    env = make_env()  # ProcessingTime default
    (
        env.from_collection([])
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .allowed_lateness(Time.seconds(2))
        .sum(2)
        .print()
    )
    f = next(f for f in env.analyze() if f.code == "TSM004")
    assert "processing-time" in f.message


def test_tsm005_nonreplayable_source_under_restart():
    env = make_env(restart_strategy=fixed_delay(3))
    # socket source constructs lazily: no connection until execute()
    text = env.socket_text_stream("localhost", 19999)
    text.map(parse).filter(lambda v: v.f2 > 90).print()
    f = next(f for f in env.analyze() if f.code == "TSM005")
    assert f.severity == ERROR
    assert "SocketTextSource" in f.message


def test_tsm005_silent_for_replayable_source():
    env = good_job(make_env(restart_strategy=fixed_delay(3)))
    assert "TSM005" not in codes(env.analyze())


def test_tsm006_compaction_on_mesh():
    # explicit capacity on p>1: WARN
    env = good_job(make_env(parallelism=2, compaction_capacity=128))
    f = next(f for f in env.analyze() if f.code == "TSM006")
    assert f.severity == WARN
    # default capacity: same fact, INFO (nothing was asked for)
    env = good_job(make_env(parallelism=2))
    f = next(f for f in env.analyze() if f.code == "TSM006")
    assert f.severity == INFO
    # single chip: silent
    env = good_job(make_env(compaction_capacity=128))
    assert "TSM006" not in codes(env.analyze())


def test_tsm008_tenant_chain_drift():
    from tpustream.jobs.chapter6_tenant_fleet import make_fleet, make_rules

    server = make_fleet({"t0": 90.0})
    env = StreamExecutionEnvironment(server.config)
    server.build_job(env)
    assert "TSM008" not in codes(env.analyze())  # honest fleet: clean

    # swap the fleet template out from under the built chain
    from tpustream.tenancy import TenantPlan

    server.plan = TenantPlan(
        parse=lambda s: s,
        build=lambda stream, rules: stream.filter(lambda v: True).map(
            lambda v: v
        ),
        rules=make_rules(),
    )
    f = next(f for f in env.analyze() if f.code == "TSM008")
    assert f.severity == ERROR


def test_tsm008_tolerates_leading_flat_map():
    """A fleet template that leads with flat_map lowers it onto the raw
    host stage BEFORE the lazily attached parse map: the template check
    must fold those leading nodes back into the signature, not skip (or
    flag) the chain."""
    from tpustream import JobServer, TenantPlan
    from tpustream.jobs.chapter6_tenant_fleet import make_rules
    from tpustream.jobs.chapter6_tenant_fleet import parse as c6_parse

    plan = TenantPlan(
        parse=c6_parse,
        build=lambda s, r: s.flat_map(lambda line: line.split("|")).filter(
            lambda v: v.f2 > r.param("threshold")
        ),
        rules=make_rules(),
        tenant_capacity=4,
    )
    server = JobServer(plan, config=StreamConfig())
    server.add_tenant("t0", rules={"threshold": 90.0})
    env = StreamExecutionEnvironment(server.config)
    server.build_job(env)
    assert "TSM008" not in codes(env.analyze())

    # drift UNDER the flat_map prefix is still caught
    server.plan = TenantPlan(
        parse=c6_parse,
        build=lambda s, r: s.flat_map(lambda line: line.split("|")).map(
            lambda v: v
        ),
        rules=make_rules(),
        tenant_capacity=4,
    )
    assert "TSM008" in codes(env.analyze())


def test_tsm010_window_process_forces_depth_one():
    env = make_env(async_depth=2)
    (
        env.from_collection([])
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .process(lambda key, ctx, elems: [])
        .print()
    )
    f = next(f for f in env.analyze() if f.code == "TSM010")
    assert f.severity == INFO


def test_tsm013_unproduced_side_output_tag():
    env = make_env()
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    out = (
        env.from_collection([])
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .sum(2)
    )
    out.print()
    out.get_side_output(OutputTag("never-declared")).print()
    f = next(f for f in env.analyze() if f.code == "TSM013")
    assert f.severity == ERROR


def test_tsm014_planner_rejection_catch_all():
    env = make_env()
    stream = env.from_collection([])
    bogus = Node("transmogrify", stream.node, {})
    env._register_sink(Node("sink_print", bogus, {}))
    f = next(f for f in env.analyze() if f.code == "TSM014")
    assert f.severity == ERROR
    assert "planner" in f.message


def test_tsm015_health_rule_unknown_series():
    from tpustream.obs.health import AlertRule

    bad = AlertRule(name="typo", metric="step_tme_s:p99", value=1.0)
    obs = ObsConfig(enabled=True, health_rules=(bad,))
    env = good_job(make_env(obs=obs))
    f = next(f for f in env.analyze() if f.code == "TSM015")
    assert f.severity == WARN
    assert "step_tme_s" in f.message
    # dict-form rules are coerced the same way
    obs = ObsConfig(
        enabled=True,
        health_rules=({"name": "d", "metric": "no_such_series"},),
    )
    env = good_job(make_env(obs=obs))
    assert "TSM015" in codes(env.analyze())


def test_tsm015_known_series_and_patterns_are_clean():
    from tpustream.obs.health import AlertRule

    good_rules = (
        AlertRule(name="slow", metric="step_time_s:p99", value=0.5),
        AlertRule(name="sink", metric="sink0_e2e_latency_ms:p99", value=9.0),
        AlertRule(name="op", metric="operator_window_steps", kind="absence"),
        AlertRule(name="ts", metric="tenant_step_share", value=0.8),
    )
    obs = ObsConfig(enabled=True, health_rules=good_rules)
    env = good_job(make_env(obs=obs))
    assert "TSM015" not in codes(env.analyze())


def test_tsm015_tenant_slo_series_are_cataloged():
    """The series compile_tenant_slo emits must stay in the catalog —
    this is the drift guard for the per-tenant SLO engine."""
    from tpustream.jobs.chapter6_tenant_fleet import make_fleet
    from tpustream.obs.slo import TenantSLO

    server = make_fleet({"t0": 90.0})
    server.set_tenant_slo("t0", TenantSLO(p99_ms=50.0, max_error_rate=0.01))
    env = StreamExecutionEnvironment(server.config)
    server.build_job(env)
    assert "TSM015" not in codes(env.analyze())


def test_tsm016_lanes_over_nonsplittable_source():
    from tpustream.runtime.sources import SocketTextSource

    env = make_env(ingest_lanes=2)
    (
        env.add_source(SocketTextSource("localhost", 9999))
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .sum(2)
        .print()
    )
    f = next(f for f in env.analyze() if f.code == "TSM016")
    assert f.severity == ERROR
    assert "not line-splittable" in f.message


def test_tsm016_lanes_exceeding_host_cores():
    from tpustream.obs import resources

    lanes = resources.usable_cores() + 2
    env = good_job(make_env(ingest_lanes=lanes))
    f = next(f for f in env.analyze() if f.code == "TSM016")
    assert f.severity == WARN
    assert "usable core" in f.message


def test_tsm016_respects_cgroup_quota(monkeypatch):
    """The broken case the raw os.cpu_count() check missed: a 96-core
    box under a 2-core cgroup quota must WARN at 4 lanes."""
    from tpustream.obs import resources

    monkeypatch.setattr(resources, "affinity_cores", lambda: 96)
    monkeypatch.setattr(resources, "cgroup_quota_cores", lambda *a: 2.0)
    env = good_job(make_env(ingest_lanes=4))
    f = next(
        f for f in env.analyze()
        if f.code == "TSM016" and "usable core" in f.message
    )
    assert f.severity == WARN
    assert "ingest_lanes=4" in f.message and "2 usable" in f.message
    # clean twin: the same box with no quota has cores to spare
    monkeypatch.setattr(resources, "cgroup_quota_cores", lambda *a: None)
    env = good_job(make_env(ingest_lanes=4))
    assert not [
        f for f in env.analyze()
        if f.code == "TSM016" and "usable core" in f.message
    ]


def test_tsm016_lanes_under_multihost(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    env = good_job(make_env(ingest_lanes=2))
    f = next(
        f for f in env.analyze()
        if f.code == "TSM016" and "multi-host" in f.message
    )
    assert f.severity == INFO


def test_tsm016_clean_configurations():
    from tpustream.runtime.sources import SocketTextSource

    # lanes=1: the rule never looks at the source
    env = make_env()
    (
        env.add_source(SocketTextSource("localhost", 9999))
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .sum(2)
        .print()
    )
    assert "TSM016" not in codes(env.analyze())
    # raw-mode socket IS splittable: no ERROR (a core-count WARN may
    # still fire on small hosts)
    env = make_env(ingest_lanes=2)
    (
        env.add_source(SocketTextSource("localhost", 9999, raw=True))
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .sum(2)
        .print()
    )
    assert ERROR not in [
        f.severity for f in env.analyze() if f.code == "TSM016"
    ]


def test_tsm017_lane_restarts_over_nonreplayable_source():
    from tpustream.runtime.sources import SocketTextSource

    # raw-mode socket is splittable (lanes engage) but NOT replayable:
    # the watchdog escalation rung has nothing to replay
    env = make_env(ingest_lanes=2, ingest_lane_restarts=2)
    (
        env.add_source(SocketTextSource("localhost", 9999, raw=True))
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .sum(2)
        .print()
    )
    f = next(f for f in env.analyze() if f.code == "TSM017")
    assert f.severity == ERROR
    assert "not replayable" in f.message


def test_tsm017_lane_restarts_over_nonsplittable_source():
    from tpustream.runtime.sources import SocketTextSource

    env = make_env(ingest_lanes=2, ingest_lane_restarts=1)
    (
        env.add_source(SocketTextSource("localhost", 9999))
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .sum(2)
        .print()
    )
    msgs = [f.message for f in env.analyze() if f.code == "TSM017"]
    assert any("not line-splittable" in m for m in msgs)


def test_tsm017_stall_limit_below_frame_deadline():
    env = good_job(make_env(
        ingest_lanes=2, max_batch_delay_ms=5.0,
        ingest_lane_stall_limit_ms=8.0,
    ))
    f = next(f for f in env.analyze() if f.code == "TSM017")
    assert f.severity == WARN
    assert "recovered in a loop" in f.message


def test_tsm017_clean_configurations():
    # replayable in-memory source + default stall limit: no findings
    env = good_job(make_env(ingest_lanes=2, ingest_lane_restarts=2))
    assert "TSM017" not in codes(env.analyze())
    # restarts=0 over a non-replayable source: the budget never spends,
    # so the rule stays quiet (TSM016 still owns the splittability story)
    from tpustream.runtime.sources import SocketTextSource

    env = make_env(ingest_lanes=2, ingest_lane_restarts=0)
    (
        env.add_source(SocketTextSource("localhost", 9999, raw=True))
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .sum(2)
        .print()
    )
    assert "TSM017" not in codes(env.analyze())
    # stall detection disabled entirely: no WARN either
    env = good_job(make_env(
        ingest_lanes=2, ingest_lane_stall_limit_ms=0.0
    ))
    assert "TSM017" not in [
        f.code for f in env.analyze() if f.severity == WARN
    ]


def test_tsm018_trace_sampling_without_marker_carrier():
    # sampling on, but obs disabled: nothing can carry the trace probes
    env = good_job(make_env(obs=ObsConfig(trace_sample_rate=0.01)))
    f = next(f for f in env.analyze() if f.code == "TSM018")
    assert f.severity == ERROR
    # obs on but the marker interval is zero: same dead letterbox
    env = good_job(make_env(obs=ObsConfig(
        enabled=True, latency_marker_interval_ms=0.0,
        trace_sample_rate=0.01,
    )))
    assert "TSM018" in codes(env.analyze())


def test_tsm018_rate_outside_unit_interval():
    env = good_job(make_env(obs=ObsConfig(
        enabled=True, latency_marker_interval_ms=100.0,
        trace_sample_rate=5.0,
    )))
    f = next(f for f in env.analyze() if f.code == "TSM018")
    assert f.severity == WARN


def test_tsm018_clean_configurations():
    # sampling off entirely: silent
    env = good_job(make_env(obs=ObsConfig(enabled=True)))
    assert "TSM018" not in codes(env.analyze())
    # sampling with a live marker carrier and a sane rate: silent
    env = good_job(make_env(obs=ObsConfig(
        enabled=True, latency_marker_interval_ms=100.0,
        trace_sample_rate=0.01,
    )))
    assert "TSM018" not in codes(env.analyze())


def test_tsm019_dead_resource_sampler():
    # resources on but no snapshot ticks to drive the sampler: ERROR
    env = good_job(make_env(obs=ObsConfig(enabled=True, resources=True)))
    f = next(f for f in env.analyze() if f.code == "TSM019")
    assert f.severity == ERROR
    assert "dead sampler" in f.message
    # resources on with obs off entirely: same dead sampler
    env = good_job(make_env(obs=ObsConfig(resources=True)))
    assert any(
        f.code == "TSM019" and f.severity == ERROR for f in env.analyze()
    )


def test_tsm019_lane_sweep_without_resources():
    env = good_job(make_env(
        ingest_lanes=2,
        obs=ObsConfig(enabled=True, snapshot_interval_s=0.5),
    ))
    f = next(f for f in env.analyze() if f.code == "TSM019")
    assert f.severity == INFO
    assert "resource sampling off" in f.message


def test_tsm019_clean_configuration():
    env = good_job(make_env(
        ingest_lanes=2,
        obs=ObsConfig(enabled=True, resources=True,
                      snapshot_interval_s=0.5),
    ))
    assert "TSM019" not in codes(env.analyze())


def test_tsm051_dead_ledger():
    # ledger explicitly on but obs off: residuals are never evaluated
    env = good_job(make_env(obs=ObsConfig(ledger=True)))
    f = next(f for f in env.analyze() if f.code == "TSM051")
    assert f.severity == ERROR
    assert "dead ledger" in f.message
    # obs on but no snapshot ticks to drive the evaluator: same shape
    env = good_job(make_env(obs=ObsConfig(
        enabled=True, snapshot_interval_s=0.0, ledger=True,
    )))
    assert any(
        f.code == "TSM051" and f.severity == ERROR for f in env.analyze()
    )


def test_tsm051_anchors_never_land():
    # explicit ledger + digests but no checkpointing: sha256 folded per
    # row, no anchor ever written -> WARN
    env = good_job(make_env(obs=ObsConfig(
        enabled=True, snapshot_interval_s=0.5, ledger=True,
    )))
    f = next(f for f in env.analyze() if f.code == "TSM051")
    assert f.severity == WARN
    assert "anchor" in f.message


def test_tsm051_clean_configurations():
    # the auto-on default (ledger=None) must never be noisy, even
    # without checkpointing
    env = good_job(make_env(obs=ObsConfig(enabled=True)))
    assert "TSM051" not in codes(env.analyze())
    # explicit ledger with digests riding real checkpoints: silent
    env = good_job(make_env(
        checkpoint_dir="/tmp/tsm051-ck", checkpoint_interval_batches=2,
        obs=ObsConfig(enabled=True, snapshot_interval_s=0.5, ledger=True),
    ))
    assert "TSM051" not in codes(env.analyze())
    # explicit ledger without digests needs no checkpoints: silent
    env = good_job(make_env(obs=ObsConfig(
        enabled=True, snapshot_interval_s=0.5, ledger=True,
        ledger_digests=False,
    )))
    assert "TSM051" not in codes(env.analyze())


def test_tsm052_dead_drill():
    # drill interval set but obs off: the drill never arms
    env = good_job(make_env(
        checkpoint_dir="/tmp/tsm052-ck", checkpoint_interval_batches=1,
        restore_drill_interval_s=5.0,
    ))
    f = next(f for f in env.analyze() if f.code == "TSM052")
    assert f.severity == ERROR
    assert "dead drill" in f.message
    # obs on but checkpointing off: no snapshot to ever exercise
    env = good_job(make_env(
        restore_drill_interval_s=5.0, obs=ObsConfig(enabled=True),
    ))
    assert any(
        f.code == "TSM052" and f.severity == ERROR for f in env.analyze()
    )


def test_tsm052_drill_faster_than_snapshots():
    env = good_job(make_env(
        checkpoint_dir="/tmp/tsm052-ck", checkpoint_interval_batches=1,
        restore_drill_interval_s=0.5,
        obs=ObsConfig(enabled=True, snapshot_interval_s=5.0),
    ))
    f = next(f for f in env.analyze() if f.code == "TSM052")
    assert f.severity == WARN
    assert "shorter than" in f.message


def test_tsm052_clean_configurations():
    # drill off: silent regardless of the rest
    env = good_job(make_env(obs=ObsConfig(enabled=True)))
    assert "TSM052" not in codes(env.analyze())
    # fully armed drill at a sane cadence: silent
    env = good_job(make_env(
        checkpoint_dir="/tmp/tsm052-ck", checkpoint_interval_batches=1,
        restore_drill_interval_s=10.0,
        obs=ObsConfig(enabled=True, snapshot_interval_s=5.0),
    ))
    assert "TSM052" not in codes(env.analyze())


def test_tsm053_stranded_savepoint_request():
    # a savepoint request pending with no checkpoint_dir: the executor
    # can never consume it (the request predates a config replace that
    # dropped the directory)
    env = good_job(make_env(checkpoint_dir="/tmp/tsm053-ck"))
    env.savepoint("pre-rescale")
    env.config = env.config.replace(checkpoint_dir="")
    f = next(f for f in env.analyze() if f.code == "TSM053")
    assert f.severity == ERROR
    assert "pre-rescale" in f.message


def test_tsm053_retention_below_inflight_budget():
    env = good_job(make_env(
        checkpoint_dir="/tmp/tsm053-ck", checkpoint_interval_batches=1,
        checkpoint_keep=1, checkpoint_async_inflight=3,
    ))
    f = next(f for f in env.analyze() if f.code == "TSM053")
    assert f.severity == WARN
    assert "in-flight" in f.message


def test_tsm053_keep_below_floor_is_visible():
    env = good_job(make_env(
        checkpoint_dir="/tmp/tsm053-ck", checkpoint_interval_batches=1,
        checkpoint_keep=0,
    ))
    f = next(f for f in env.analyze() if f.code == "TSM053")
    assert f.severity == WARN
    assert "clamps to 1" in f.message


def test_tsm053_clean_configurations():
    # defaults: silent
    env = good_job(make_env(
        checkpoint_dir="/tmp/tsm053-ck", checkpoint_interval_batches=1,
    ))
    assert "TSM053" not in codes(env.analyze())
    # retention covering the in-flight budget: silent
    env = good_job(make_env(
        checkpoint_dir="/tmp/tsm053-ck", checkpoint_interval_batches=1,
        checkpoint_keep=4, checkpoint_async_inflight=2,
    ))
    assert "TSM053" not in codes(env.analyze())
    # savepoint request with a directory to land in: silent
    env = good_job(make_env(checkpoint_dir="/tmp/tsm053-ck"))
    env.savepoint("ok")
    assert "TSM053" not in codes(env.analyze())


def test_findings_sorted_errors_first():
    # one ERROR (TSM013) + one INFO (TSM010) in a single graph
    env = make_env(async_depth=2)
    out = (
        env.from_collection([])
        .map(parse)
        .key_by(0)
        .time_window(Time.seconds(5))
        .process(lambda key, ctx, elems: [])
    )
    out.print()
    out.get_side_output(OutputTag("nope")).print()
    found = env.analyze()
    ranks = [{"error": 2, "warn": 1, "info": 0}[f.severity] for f in found]
    assert ranks == sorted(ranks, reverse=True)
    assert found[0].severity == ERROR


# ---------------------------------------------------------------------------
# purity analyzer
# ---------------------------------------------------------------------------


def test_tsm020_nondeterministic_call():
    import random

    def jitter(v):
        return Tuple3(v.f0, v.f1, v.f2 * random.random())

    env = make_env()
    env.from_collection([]).map(parse).map(jitter).print()
    assert "TSM020" in codes(env.analyze())


def test_tsm021_mutable_closure():
    seen = []

    def remember(v):
        seen.append(v)
        return v

    found = analyze_callable(remember, "map", device=True)
    assert codes(found) == ["TSM021"]
    # immutable capture: silent
    threshold = 90.0
    assert analyze_callable(lambda v: v.f2 > threshold, "filter") == []


def test_tsm021_global_write():
    def bump(v):
        global _BUMP_COUNT
        _BUMP_COUNT += 1
        return v

    assert "TSM021" in codes(analyze_callable(bump, "map"))


def test_tsm022_print_in_device_fn():
    def shout(v):
        print("saw", v)
        return v

    assert "TSM022" in codes(analyze_callable(shout, "map", device=True))
    # host stages may print: the device-only rule stays quiet
    assert "TSM022" not in codes(
        analyze_callable(shout, "map", device=False)
    )


def test_tsm023_host_callback_in_device_fn():
    def peek(v):
        import jax

        jax.debug.print("v={}", v)
        return v

    found = analyze_callable(peek, "map", device=True)
    assert "TSM023" in codes(found)
    assert next(f for f in found if f.code == "TSM023").severity == ERROR


def test_tsm024_dtype_widening():
    widen = lambda v: v * np.float64(2.0)  # noqa: E731
    found = check_dtype_widening(widen, ["f64"], value_dtype="float32")
    assert codes(found) == ["TSM024"]
    # a dtype-preserving map is silent
    keep = lambda v: v * np.float32(2.0)  # noqa: E731
    assert check_dtype_widening(keep, ["f64"], value_dtype="float32") == []
    # at float64 (the default) there is nothing wider to widen to
    assert check_dtype_widening(widen, ["f64"], value_dtype="float64") == []


def test_purity_skips_unreadable_callables():
    # builtins have no retrievable source: silence, never a crash
    assert analyze_callable(len, "map", device=True) == []


# ---------------------------------------------------------------------------
# strict mode + obs integration
# ---------------------------------------------------------------------------


def test_strict_analysis_blocks_before_compile():
    env = make_env(strict_analysis=True)
    stream = env.from_collection(["1563452051 10.8.22.1 cpu2 99.2"])
    KeyedStream(env, stream.map(parse).node).max(2).print()
    with pytest.raises(PlanAnalysisError) as ei:
        env.execute("broken")
    assert any(f.code == "TSM001" for f in ei.value.findings)
    assert "strict_analysis" in str(ei.value)
    # submission never got far enough to attach metrics
    assert env.metrics is None


def _remember(seen):
    """A host map that captures a mutable list: TSM021, a WARN."""

    def remember(v):
        seen.append(v)
        return v

    return remember


def test_strict_analysis_off_by_default_and_warns_pass():
    env = make_env(strict_analysis=True)
    text = env.add_source(ReplaySource(["1563452051 10.8.22.1 cpu2 99.2"]))
    handle = (
        text.map(parse).map(_remember([])).filter(lambda v: v.f2 > 90)
        .collect()
    )
    assert "TSM021" in codes(env.analyze())
    env.execute("warn-only")  # TSM021 is WARN: strict mode still runs
    assert handle.items == [("10.8.22.1", "cpu2", 99.2)]


def test_obs_records_findings_and_clamp():
    env = make_env(ingest_lanes=0, obs=ObsConfig(enabled=True))
    text = env.add_source(ReplaySource(["1563452051 10.8.22.1 cpu2 99.2"]))
    handle = (
        text.map(parse).map(_remember([])).filter(lambda v: v.f2 > 90)
        .collect()
    )
    res = env.execute("obs-findings")
    assert handle.items == [("10.8.22.1", "cpu2", 99.2)]
    series = {
        (s["name"], s["labels"].get("code")): s["value"]
        for s in res.metrics.obs_snapshot()["metrics"]["series"]
        if s["name"] == "analysis_findings_total"
    }
    assert series[("analysis_findings_total", "TSM021")] == 1
    kinds = [e["kind"] for e in res.metrics.job_obs.flight.events()]
    assert "analysis_finding" in kinds
    assert "config_clamped" in kinds
    clamp = next(
        e for e in res.metrics.job_obs.flight.events()
        if e["kind"] == "config_clamped"
    )
    assert clamp["knob"] == "ingest_lanes"
    assert clamp["effective"] == 1


# ---------------------------------------------------------------------------
# lint CLI
# ---------------------------------------------------------------------------


def test_lint_cli_all_chapters_clean():
    out = io.StringIO()
    assert lint_main([], out=out) == 0
    text = out.getvalue()
    for ch in (
        "chapter1_threshold", "chapter2_avg", "chapter2_max",
        "chapter2_median", "chapter3_bandwidth",
        "chapter3_bandwidth_eventtime", "chapter4_cep_alert",
        "chapter5_dynamic_rules", "chapter6_tenant_fleet",
    ):
        assert f"tpustream.jobs.{ch}: ok (0 errors" in text


def test_lint_cli_exit_codes(tmp_path, monkeypatch):
    # rc=2: module does not import
    out = io.StringIO()
    assert lint_main(["no.such.module"], out=out) == 2
    assert "IMPORT FAILED" in out.getvalue()

    # rc=1: a job module whose graph has an ERROR finding
    (tmp_path / "badjob.py").write_text(textwrap.dedent(
        """
        from tpustream import StreamExecutionEnvironment
        from tpustream.api.datastream import KeyedStream

        def lint_env():
            env = StreamExecutionEnvironment.get_execution_environment()
            stream = env.from_collection([])
            KeyedStream(env, stream.node).max(0).print()
            return env
        """
    ))
    monkeypatch.syspath_prepend(str(tmp_path))
    out = io.StringIO()
    assert lint_main(["badjob"], out=out) == 1
    assert "TSM001" in out.getvalue()

    # no lint_env hook: skipped, rc=0
    (tmp_path / "hookless.py").write_text("x = 1\n")
    out = io.StringIO()
    assert lint_main(["hookless"], out=out) == 0
    assert "skipped" in out.getvalue()


def test_catalog_is_stable():
    """Codes are append-only API: the documented set must stay intact
    (docs/analysis.md renders from CATALOG)."""
    expected = {
        "TSM001", "TSM002", "TSM003", "TSM004", "TSM005", "TSM006",
        "TSM007", "TSM008", "TSM010",
        "TSM013", "TSM014", "TSM015", "TSM016", "TSM017", "TSM018",
        "TSM019", "TSM020", "TSM021",
        "TSM022", "TSM023", "TSM024", "TSM025", "TSM030", "TSM031",
        "TSM032", "TSM033", "TSM034", "TSM040", "TSM041", "TSM042",
        "TSM043", "TSM044", "TSM045", "TSM046", "TSM047", "TSM051",
        "TSM052", "TSM053",
    }
    assert expected <= set(CATALOG)
    # retired with the options they checked; never reused
    assert not {"TSM009", "TSM011", "TSM012"} & set(CATALOG)
    for code, rule in CATALOG.items():
        assert rule.code == code
        assert rule.severity in (ERROR, WARN, INFO)
        assert rule.title and rule.rationale and rule.fix_hint


# ---------------------------------------------------------------------------
# schema inference over the whole tutorial fleet + machine formats
# ---------------------------------------------------------------------------


CHAPTERS = (
    "chapter1_threshold", "chapter2_avg", "chapter2_max",
    "chapter2_median", "chapter3_bandwidth",
    "chapter3_bandwidth_eventtime", "chapter4_cep_alert",
    "chapter5_dynamic_rules", "chapter6_tenant_fleet",
)


def test_all_chapters_schema_clean():
    """End-to-end schema inference over every chapter job: zero TSM03x
    findings, and the chapter-1/chapter-3 sink schemas stay pinned
    (they are the tutorial's documented record shapes)."""
    import importlib

    from tpustream.analysis import infer_schemas

    schema_codes = {"TSM030", "TSM031", "TSM032", "TSM033", "TSM034"}
    sink_kinds = {}
    for ch in CHAPTERS:
        mod = importlib.import_module(f"tpustream.jobs.{ch}")
        env = mod.lint_env()
        found = set(codes(env.analyze())) & schema_codes
        assert not found, f"{ch}: unexpected schema findings {found}"
        rep = infer_schemas(env)
        sink_kinds[ch] = rep.sink.kinds if rep.sink is not None else None
    assert sink_kinds["chapter1_threshold"] == ["str", "str", "f64"]
    assert sink_kinds["chapter3_bandwidth"] == ["str", "i64"]


def test_lint_cli_json_round_trips_catalog():
    """--format json is the CI contract: one parseable document whose
    finding records carry exactly the stable keys, with codes/severities
    that round-trip against the CATALOG."""
    import json as _json

    out = io.StringIO()
    assert lint_main(["--format", "json"], out=out) == 0
    doc = _json.loads(out.getvalue())
    assert doc["exit"] == 0
    assert {r["module"].rsplit(".", 1)[1] for r in doc["modules"]} == set(
        CHAPTERS
    )
    for rec in doc["modules"]:
        assert rec["status"] == "ok"
        for f in rec["findings"]:
            assert set(f) == {
                "code", "severity", "node", "message", "fix_hint",
            }
            assert f["code"] in CATALOG
            assert f["severity"] == CATALOG[f["code"]].severity


def test_lint_cli_github_annotations(tmp_path, monkeypatch):
    (tmp_path / "ghjob.py").write_text(textwrap.dedent(
        """
        from tpustream import StreamExecutionEnvironment
        from tpustream.api.datastream import KeyedStream

        def lint_env():
            env = StreamExecutionEnvironment.get_execution_environment()
            stream = env.from_collection([])
            KeyedStream(env, stream.node).max(0).print()
            return env
        """
    ))
    monkeypatch.syspath_prepend(str(tmp_path))
    out = io.StringIO()
    assert lint_main(["ghjob", "--format", "github"], out=out) == 1
    lines = [l for l in out.getvalue().splitlines() if l]
    assert any(
        l.startswith("::error title=TSM001 (ghjob)::") for l in lines
    )
    # annotations are single-line by construction
    assert all(l.startswith("::") for l in lines)
