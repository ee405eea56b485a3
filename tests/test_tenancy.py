"""Multi-tenant job server (tpustream/tenancy, docs/multitenancy.md):
N logical jobs multiplexed onto ONE compiled mesh step. The contracts
pinned here:

* a 64-tenant fleet runs through one compiled program — the obs compile
  registry shows ZERO ``config_change`` recompiles, because tenant rule
  rows are data ([T] vectors gathered per record), never constants;
* a tenant's demuxed output is byte-identical (repr-equal Tuple fields)
  to running its job ALONE with the same records and rule timeline;
* ``add_tenant`` / ``remove_tenant`` / ``update_tenant_rules``
  mid-stream land at exact record boundaries, zero recompiles;
* a quota breach diverts to the tenant's ``quota_exceeded`` side output
  without perturbing any other tenant's records;
* admitting slots past the plan's capacity grows the rule vectors with
  the cause-tagged rebuild discipline (``tenant_capacity_grown`` flight
  event, ``operator_recompile_cause{cause="tenant_capacity_growth"}``)
  — never a silent retrace;
* the fleet survives an injected ``tenant_apply`` crash with
  byte-identical per-tenant output, and checkpoints carry the tenant
  table + per-tenant rule vectors (format v10).

Slow tier: the p=8 mesh produces identical per-tenant output, and a
supervised fleet crash mid-stream recovers exactly-once.
"""

import glob
import os

import pytest

from tpustream import (
    JobServer,
    RuleSet,
    RuleUpdate,
    StreamExecutionEnvironment,
    TenantPlan,
    TenantQuota,
    Tuple2,
    Tuple3,
)
from tpustream.broadcast.rules import TENANT_VALUES_KEY
from tpustream.config import ObsConfig, StreamConfig
from tpustream.jobs import chapter6_tenant_fleet as c6
from tpustream.runtime.sources import ReplaySource
from tpustream.runtime.supervisor import fixed_delay
from tpustream.tenancy import TenantShapeError
from tpustream.testing import FaultInjector, FaultPoint

# fleet runs re-dispatch donated-buffer executables many times per test;
# use a cold per-test compilation cache (the test_key_growth.py
# segfault-avoidance pattern, via conftest marker)
pytestmark = pytest.mark.fresh_cache


def make_server(capacity=64, batch_size=8, obs=False, ckdir=None,
                injector=None, **over):
    cfg = StreamConfig(batch_size=batch_size, **over)
    if obs:
        cfg = cfg.replace(obs=ObsConfig(enabled=True))
    if ckdir is not None:
        cfg = cfg.replace(
            checkpoint_dir=str(ckdir), checkpoint_interval_batches=1
        )
    if injector is not None:
        cfg = injector.install(cfg)
    return JobServer(c6.make_plan(capacity), config=cfg)


def run_solo(lines, updates, batch_size=8):
    """The SAME job a tenant runs, alone: chapter-6 template chain with
    its rule timeline as a plain chapter-5 broadcast schedule.
    ``updates`` is [(after_records, value)] including the initial
    threshold at position 0 — exactly what add_tenant schedules."""
    env = StreamExecutionEnvironment(StreamConfig(batch_size=batch_size))
    rules = c6.make_rules()
    env.add_source(ReplaySource(
        [RuleUpdate("threshold", v, pos) for pos, v in updates]
    )).broadcast(rules)
    handle = c6.build(
        env.from_collection(lines).map(c6.parse), rules
    ).collect()
    env.execute("solo")
    return handle.items


def reprs(items):
    return [repr(x) for x in items]


def recompile_causes(result, cause=None):
    series = result.metrics.obs_snapshot()["metrics"]["series"]
    return [
        s for s in series
        if s["name"] == "operator_recompile_cause"
        and (cause is None or s["labels"].get("cause") == cause)
    ]


# ---------------------------------------------------------------------------
# the acceptance gate: 64 tenants, one compiled program
# ---------------------------------------------------------------------------
def test_64_tenants_one_program_zero_recompiles():
    """64 same-shape tenants with 64 different thresholds through one
    compiled program: every tenant's output matches its host oracle and
    the compile registry shows zero config_change (and zero capacity
    growth) rebuilds."""
    thresholds = {f"t{i:02d}": 80.0 + (i % 20) for i in range(64)}
    srv = make_server(capacity=64, batch_size=64, obs=True)
    for tenant, thr in thresholds.items():
        srv.add_tenant(tenant, rules={"threshold": thr})
    per_tenant = {t: c6.tenant_lines(t, 8) for t in thresholds}
    # interleave ingestion round-robin so batches mix tenants
    for i in range(8):
        for t in thresholds:
            srv.ingest(t, [per_tenant[t][i]])
    res = srv.run("fleet-64")
    for tenant, thr in thresholds.items():
        want = c6.expected(tenant, per_tenant[tenant], thr, [(0, thr)])
        assert reprs(srv.output(tenant)) == reprs(want), tenant
    assert recompile_causes(res, "config_change") == []
    assert recompile_causes(res, "tenant_capacity_growth") == []


@pytest.mark.parametrize("batch_size", [3, 8, 64])
def test_demux_output_batch_size_invariant(batch_size):
    thresholds = {"a": 85.0, "b": 92.0}
    srv = make_server(batch_size=batch_size)
    for t, thr in thresholds.items():
        srv.add_tenant(t, rules={"threshold": thr})
        srv.ingest(t, c6.tenant_lines(t, 10))
    srv.run("fleet-bs")
    for t, thr in thresholds.items():
        want = c6.expected(t, c6.tenant_lines(t, 10), thr, [(0, thr)])
        assert reprs(srv.output(t)) == reprs(want)


# ---------------------------------------------------------------------------
# solo parity: a tenant can't tell it shares the program
# ---------------------------------------------------------------------------
def test_per_tenant_output_byte_identical_to_solo_run():
    """Three tenants with interleaved ingestion and one mid-stream
    threshold update each: every tenant's demuxed output is repr-equal
    to running its job alone with the same records and timeline."""
    srv = make_server(batch_size=4)
    fleets = {"acme": 84.0, "globex": 90.0, "initech": 96.0}
    lines = {t: c6.tenant_lines(t, 12, base=78.0 + i * 2)
             for i, t in enumerate(fleets)}
    for t, thr in fleets.items():
        srv.add_tenant(t, rules={"threshold": thr})
        srv.ingest(t, lines[t][:6])
    srv.update_tenant_rules("globex", {"threshold": 79.0})
    for t in fleets:
        srv.ingest(t, lines[t][6:])
    srv.run("fleet-parity")
    for t, thr in fleets.items():
        updates = [(0, thr)]
        if t == "globex":
            updates.append((6, 79.0))  # local position of the update
        solo = run_solo(lines[t], updates)
        assert reprs(srv.output(t)) == reprs(solo), t
        assert reprs(solo) == reprs(
            c6.expected(t, lines[t], thr, updates)
        ), t


def test_hot_add_remove_update_mid_stream_record_exact():
    """The full hot control plane in one run, zero recompiles: a tenant
    added mid-stream, one removed mid-stream (its later records drop
    in-step), one updated mid-stream — all record-exact vs solo runs."""
    srv = make_server(batch_size=4, obs=True)
    srv.add_tenant("early", rules={"threshold": 85.0})
    srv.ingest("early", c6.tenant_lines("early", 8))
    # hot add after the stream started
    srv.add_tenant("late", rules={"threshold": 88.0})
    srv.ingest("late", c6.tenant_lines("late", 8))
    # hot update for early: local position 8 (it ingested 8 records)
    srv.update_tenant_rules("early", {"threshold": 99.0})
    srv.ingest("early", c6.tenant_lines("early", 8, base=90.0))
    # hot remove late: its remaining records must drop in-step
    srv.remove_tenant("late")
    srv.ingest("late", c6.tenant_lines("late", 8, base=99.0))
    res = srv.run("fleet-hot")

    early_lines = c6.tenant_lines("early", 8) + c6.tenant_lines(
        "early", 8, base=90.0
    )
    assert reprs(srv.output("early")) == reprs(
        run_solo(early_lines, [(0, 85.0), (8, 99.0)])
    )
    # late: only its pre-removal records, at its own threshold
    assert reprs(srv.output("late")) == reprs(
        run_solo(c6.tenant_lines("late", 8), [(0, 88.0)])
    )
    assert recompile_causes(res, "config_change") == []
    # the per-tenant rule_version gauge got minted on tenant updates,
    # and the REMOVED tenant's series were retired at its removal
    # boundary — a gone tenant must not linger in scrapes
    series = res.metrics.obs_snapshot()["metrics"]["series"]
    rv = [s for s in series if s["name"] == "tenant_rule_version"]
    assert {s["labels"].get("tenant") for s in rv} == {"early"}
    late = [
        s for s in series if s["labels"].get("tenant") == "late"
    ]
    assert late == []


# ---------------------------------------------------------------------------
# quotas: breach diverts, nobody else notices
# ---------------------------------------------------------------------------
def test_quota_breach_side_output_does_not_perturb_others():
    srv = make_server(batch_size=4, obs=True)
    srv.add_tenant("noisy", rules={"threshold": 0.0},
                   quota=TenantQuota(max_records=5))
    srv.add_tenant("quiet", rules={"threshold": 85.0})
    noisy = c6.tenant_lines("noisy", 12)
    quiet = c6.tenant_lines("quiet", 12)
    for i in range(12):
        srv.ingest("noisy", [noisy[i]])
        srv.ingest("quiet", [quiet[i]])
    res = srv.run("fleet-quota")
    # noisy: exactly the first 5 admitted (threshold 0 passes all),
    # the other 7 raw lines on the quota_exceeded side output
    assert reprs(srv.output("noisy")) == reprs(
        c6.expected("noisy", noisy[:5], 0.0, [(0, 0.0)])
    )
    assert srv.quota_output("noisy") == noisy[5:]
    # quiet is byte-identical to a solo run — the breach cost it nothing
    assert reprs(srv.output("quiet")) == reprs(
        run_solo(quiet, [(0, 85.0)])
    )
    assert srv.quota_output("quiet") == []
    # obs surface: per-tenant admission/quota counters + fleet gauge
    series = res.metrics.obs_snapshot()["metrics"]["series"]
    by = {
        (s["name"], s["labels"].get("tenant")): s["value"] for s in series
    }
    assert by[("tenant_records_total", "noisy")] == 5
    assert by[("tenant_quota_exceeded_total", "noisy")] == 7
    assert by[("tenant_records_total", "quiet")] == 12
    assert by[("tenant_quota_exceeded_total", "quiet")] == 0
    assert by[("tenant_count", None)] == 2


# ---------------------------------------------------------------------------
# per-tenant SLOs: one noisy neighbor in a 64-tenant fleet
# ---------------------------------------------------------------------------
def _noisy_fleet(obs, slo=None):
    """64 tenants; ``t00`` floods 20x its quota (160 offered, 8
    admitted). Returns (srv, thresholds, lines)."""
    thresholds = {f"t{i:02d}": 80.0 + (i % 20) for i in range(64)}
    srv = make_server(capacity=64, batch_size=64, obs=obs)
    lines = {}
    for tenant, thr in thresholds.items():
        if tenant == "t00":
            srv.add_tenant(tenant, rules={"threshold": thr},
                           quota=TenantQuota(max_records=8))
            lines[tenant] = c6.tenant_lines(tenant, 160)
        else:
            srv.add_tenant(tenant, rules={"threshold": thr})
            lines[tenant] = c6.tenant_lines(tenant, 8)
        if slo is not None:
            srv.set_tenant_slo(tenant, slo)
        srv.ingest(tenant, lines[tenant])
    return srv, thresholds, lines


def test_noisy_neighbor_flooder_crit_others_ok():
    """The per-tenant SLO acceptance gate (docs/multitenancy.md): in a
    64-tenant fleet where ONE tenant floods 20x its quota, that
    tenant's error SLO goes CRIT with a fully burned error budget,
    every other tenant's rules stay OK on their own independent series,
    the verdict is scrapeable from ``/tenants.json``, and every
    tenant's demuxed output is byte-identical to the same fleet with
    obs off entirely."""
    import json
    import urllib.request

    from tpustream.obs import MetricsServer, TenantSLO

    slo = TenantSLO(p99_ms=1e6, max_error_rate=0.01,
                    budget_window_s=60.0)
    srv, thresholds, lines = _noisy_fleet(obs=True, slo=slo)
    res = srv.run("fleet-noisy")

    # the flooder's error rate: 152 of 160 offered records diverted
    snap = res.metrics.obs_snapshot()
    err = {
        s["labels"]["tenant"]: s["value"]
        for s in snap["metrics"]["series"]
        if s["name"] == "tenant_error_rate"
    }
    assert err["t00"] == pytest.approx(152 / 160)
    assert all(err[t] == 0.0 for t in thresholds if t != "t00")

    # health verdicts: flooder CRIT, burning budget; >= 60 others OK
    # (here: all 63)
    rules = {r["rule"]: r for r in snap["health"]["rules"]}
    flood = rules["slo_err[t00]"]
    assert flood["level"] == "crit"
    assert flood["labels"] == {"tenant": "t00"}
    assert flood["budget_burn"] == pytest.approx(1.0)
    ok = [
        t for t in thresholds if t != "t00"
        and rules[f"slo_err[{t}]"]["level"] == "ok"
        and rules[f"slo_p99[{t}]"]["level"] == "ok"
    ]
    assert len(ok) == 63
    # the verdict is a scrapeable series too
    state = {
        (s["labels"].get("rule"), s["labels"].get("tenant")): s["value"]
        for s in snap["metrics"]["series"]
        if s["name"] == "health_rule_state"
    }
    assert state[("slo_err[t00]", "t00")] == 2
    assert state[("slo_err[t01]", "t01")] == 0
    # the postmortem names the offending tenant: its health transition
    # is in the flight ring, filterable by tenant, and nobody else's
    flight = srv.env.metrics.job_obs.flight
    t00_events = flight.tenant_events("t00")
    assert any(
        e["kind"] == "health_transition"
        and e["rule"] == "slo_err[t00]" and e["to"] == "crit"
        for e in t00_events
    )
    assert flight.tenant_events("t01") == []

    # /tenants.json over real HTTP carries the same attribution
    server = MetricsServer(srv.env.metrics.job_obs, port=0).start()
    try:
        body = urllib.request.urlopen(
            server.url + "/tenants.json", timeout=5
        ).read()
    finally:
        server.close()
    view = json.loads(body.decode("utf-8"))
    assert view["tenant_count"] == 64
    flood_view = view["tenants"]["t00"]
    assert flood_view["quota_exceeded"] == 152
    assert flood_view["error_rate"] == pytest.approx(152 / 160)
    assert flood_view["health"]["slo_err[t00]"]["level"] == "crit"
    ok_view = [
        t for t, e in view["tenants"].items() if t != "t00"
        and all(r["level"] == "ok" for r in e["health"].values())
    ]
    assert len(ok_view) == 63

    # observing the fleet must not perturb it: byte-identical demux
    # output (and quota side output) vs the same fleet with obs OFF
    plain, _, _ = _noisy_fleet(obs=False)
    plain.run("fleet-noisy-plain")
    for t in thresholds:
        assert reprs(srv.output(t)) == reprs(plain.output(t)), t
    assert srv.quota_output("t00") == plain.quota_output("t00")
    assert srv.quota_output("t00") == lines["t00"][8:]


# ---------------------------------------------------------------------------
# capacity growth: past-capacity admission is cause-tagged, never silent
# ---------------------------------------------------------------------------
def test_tenant_capacity_growth_cause_tagged():
    """Plan capacity 4, six tenants admitted mid-stream: the rule
    vectors double 4→8 with a ``tenant_capacity_grown`` flight event and
    an ``operator_recompile_cause{cause="tenant_capacity_growth"}``
    build — and every tenant's output stays exact across the growth."""
    srv = make_server(capacity=4, batch_size=4, obs=True)
    lines = {}
    for i in range(4):
        t = f"t{i}"
        srv.add_tenant(t, rules={"threshold": 82.0 + i})
        lines[t] = c6.tenant_lines(t, 6)
        srv.ingest(t, lines[t])
    # slots 4 and 5: past capacity, mid-stream
    for i in range(4, 6):
        t = f"t{i}"
        srv.add_tenant(t, rules={"threshold": 82.0 + i})
        lines[t] = c6.tenant_lines(t, 6)
        srv.ingest(t, lines[t])
    res = srv.run("fleet-grow")
    assert srv.plan.rules.tenant_capacity == 8
    for i in range(6):
        t = f"t{i}"
        want = c6.expected(t, lines[t], 82.0 + i, [(0, 82.0 + i)])
        assert reprs(srv.output(t)) == reprs(want), t
    grown = [
        e for e in res.metrics.job_obs.flight.events()
        if e["kind"] == "tenant_capacity_grown"
    ]
    assert grown and grown[-1]["old_capacity"] == 4
    assert grown[-1]["new_capacity"] == 8
    assert recompile_causes(res, "tenant_capacity_growth")
    assert recompile_causes(res, "config_change") == []


# ---------------------------------------------------------------------------
# durability: tenant_apply crash recovery + v10 checkpoint meta
# ---------------------------------------------------------------------------
def _durable_fleet(ckdir=None, injector=None):
    srv = make_server(batch_size=4, ckdir=ckdir, injector=injector)
    srv.add_tenant("acme", rules={"threshold": 84.0})
    srv.add_tenant("globex", rules={"threshold": 92.0})
    for t in ("acme", "globex"):
        srv.ingest(t, c6.tenant_lines(t, 8))
    srv.update_tenant_rules("acme", {"threshold": 95.0})
    for t in ("acme", "globex"):
        srv.ingest(t, c6.tenant_lines(t, 8, base=88.0))
    return srv


def test_tenant_apply_crash_recovers_byte_identical(tmp_path):
    """The new fault point: crash between a tenant-scoped rule write and
    the next data batch. The supervised restart restores the tenant
    table + rule vectors from the checkpoint, replays, re-applies the
    update at the SAME boundary — per-tenant output byte-identical to an
    uninterrupted fleet, no double-apply."""
    clean = _durable_fleet()
    clean.run("fleet-clean")

    inj = FaultInjector(FaultPoint("tenant_apply", at=1))
    srv = _durable_fleet(ckdir=tmp_path, injector=inj)
    srv.run("fleet-faulted", restart_strategy=fixed_delay(3, 0.0))
    assert inj.fired == 1
    for t in ("acme", "globex"):
        assert reprs(srv.output(t)) == reprs(clean.output(t)), t
    assert srv.plan.rules.tenant_value("threshold", 0) == 95.0
    assert srv.plan.rules.tenant_value("threshold", 1) == 92.0


def test_checkpoint_carries_tenant_table_and_rule_vectors(tmp_path):
    from tpustream.runtime.checkpoint import FORMAT_VERSION, load_checkpoint

    assert FORMAT_VERSION == 13
    srv = _durable_fleet(ckdir=tmp_path)
    srv.run("fleet-ckpt")
    snaps = sorted(glob.glob(os.path.join(str(tmp_path), "ckpt-*.npz")))
    assert snaps
    ck = load_checkpoint(snaps[-1])
    assert ck.tenancy is not None
    assert ck.tenancy["tenants"] == {"acme": 0, "globex": 1}
    assert ck.tenancy["capacity"] == 64
    vecs = ck.rule_values[TENANT_VALUES_KEY]
    assert vecs["capacity"] == 64
    assert vecs["vectors"]["threshold"][0] == 95.0
    assert vecs["vectors"]["threshold"][1] == 92.0
    # the rule vectors round-trip through a fresh RuleSet
    rules = c6.make_rules()
    rules.load(ck.rule_values, ck.rule_version)
    assert rules.tenant_value("threshold", 0) == 95.0
    assert rules.version == ck.rule_version


# ---------------------------------------------------------------------------
# a KEYED fleet: namespaced key table + rolling state stay per-tenant
# ---------------------------------------------------------------------------
def _kv_parse(line):
    items = line.split(" ")
    return Tuple2(items[0], float(items[1]))


def _kv_build(stream, rules):
    return stream.key_by(0).sum(1)


def _kv_plan(capacity=4):
    rules = RuleSet()
    rules.declare("unused", 0.0, "f64")
    return TenantPlan(
        parse=_kv_parse, build=_kv_build, rules=rules,
        tenant_capacity=capacity,
    )


def test_keyed_fleet_namespaces_rolling_state_per_tenant():
    """Two tenants emit the SAME key names: the tenant namespace keeps
    their rolling sums separate, and the demuxed key strings come back
    with the namespace stripped — identical to a solo run."""
    srv = JobServer(_kv_plan(), config=StreamConfig(batch_size=4))
    srv.add_tenant("a")
    srv.add_tenant("b")
    a_lines = [f"k{i % 2} {i}" for i in range(8)]
    b_lines = [f"k{i % 2} {10 * i}" for i in range(8)]
    for i in range(8):
        srv.ingest("a", [a_lines[i]])
        srv.ingest("b", [b_lines[i]])
    srv.run("fleet-keyed")

    def solo(lines):
        env = StreamExecutionEnvironment(StreamConfig(batch_size=4))
        h = _kv_build(
            env.from_collection(lines).map(_kv_parse), None
        ).collect()
        env.execute("solo-keyed")
        return h.items

    assert reprs(srv.output("a")) == reprs(solo(a_lines))
    assert reprs(srv.output("b")) == reprs(solo(b_lines))


# ---------------------------------------------------------------------------
# fleet op coverage: flat_map / window aggregate / window process
# ---------------------------------------------------------------------------
def _expand(line):
    return line.split("|")


def test_fleet_flat_map_solo_parity():
    """A template that leads with flat_map lowers onto the RAW host
    stage (the only stage the single-job planner supports it on): the
    fan-out records stay attributed to their tenant and the demuxed
    output matches a solo run of the same chain."""

    def tpl(stream, rules):
        threshold = rules.param("threshold")
        return stream.flat_map(_expand).filter(
            lambda value: value.f2 > threshold
        )

    plan = TenantPlan(
        parse=c6.parse, build=tpl, rules=c6.make_rules(),
        tenant_capacity=4,
    )
    srv = JobServer(plan, config=StreamConfig(batch_size=4))
    thresholds = {"ta": 85.0, "tb": 95.0}
    for tenant, thr in thresholds.items():
        srv.add_tenant(tenant, rules={"threshold": thr})
    compound = {
        t: ["|".join(c6.tenant_lines(t, 8)[i:i + 2]) for i in range(0, 8, 2)]
        for t in thresholds
    }
    for i in range(4):
        for t in thresholds:
            srv.ingest(t, [compound[t][i]])
    srv.run("fleet-flatmap")

    def solo(lines, thr):
        env = StreamExecutionEnvironment(StreamConfig(batch_size=4))
        h = (
            env.from_collection(lines)
            .flat_map(_expand)
            .map(c6.parse)
            .filter(lambda value, _t=thr: value.f2 > _t)
            .collect()
        )
        env.execute("solo-flatmap")
        return h.items

    for tenant, thr in thresholds.items():
        assert reprs(srv.output(tenant)) == reprs(
            solo(compound[tenant], thr)
        ), tenant


def test_fleet_flat_map_after_parsed_op_rejected():
    """A template flat_map after a parsed-record op is rejected at
    ADMISSION (TenantPlan.validate_fleet_ops via the JobServer
    constructor), not three layers deep at run time."""
    bad = TenantPlan(
        parse=c6.parse,
        build=lambda s, r: s.filter(lambda v: v.f2 > 1).flat_map(_expand),
        rules=c6.make_rules(),
        tenant_capacity=4,
    )
    with pytest.raises(TenantShapeError, match="raw host stage"):
        JobServer(bad, config=StreamConfig(batch_size=4))


class _FleetAvg:
    """Chapter-2 style Avg whose get_result folds in the tenant's
    ``threshold`` row: aggregate fns run INSIDE the compiled step, so
    the RuleParam must gather the firing accumulator's own tenant row
    (carried as the accumulator's trailing field)."""

    def __init__(self, rules_or_const):
        self._thr = (
            rules_or_const.param("threshold")
            if isinstance(rules_or_const, RuleSet)
            else rules_or_const
        )

    def create_accumulator(self):
        return Tuple2(0, 0.0)

    def add(self, value, acc):
        return Tuple2(acc.f0 + 1, acc.f1 + value.f1)

    def merge(self, a, b):
        return Tuple2(a.f0 + b.f0, a.f1 + b.f1)

    def get_result(self, acc):
        import jax.numpy as jnp

        return jnp.where(acc.f0 == 0, 0.0, acc.f1 / acc.f0) + self._thr


def _agg_plan(capacity=4):
    rules = c6.make_rules()
    return TenantPlan(
        parse=_kv_parse,
        build=lambda s, r: s.key_by(0).count_window(2).aggregate(
            _FleetAvg(r)
        ),
        rules=rules,
        tenant_capacity=capacity,
    )


def test_fleet_window_aggregate_binds_tenant_rules():
    """Two tenants share key names and window shapes but carry very
    different thresholds: each fire's get_result must read ITS tenant's
    rule row, and the demuxed results must match solo runs with the
    threshold as a plain constant."""
    srv = JobServer(_agg_plan(), config=StreamConfig(batch_size=4))
    srv.add_tenant("a", rules={"threshold": 100.0})
    srv.add_tenant("b", rules={"threshold": 200.0})
    a_lines = [f"k{i % 2} {i}" for i in range(8)]
    b_lines = [f"k{i % 2} {10 * i}" for i in range(8)]
    for i in range(8):
        srv.ingest("a", [a_lines[i]])
        srv.ingest("b", [b_lines[i]])
    srv.run("fleet-agg")

    def solo(lines, thr):
        env = StreamExecutionEnvironment(StreamConfig(batch_size=4))
        h = (
            env.from_collection(lines)
            .map(_kv_parse)
            .key_by(0)
            .count_window(2)
            .aggregate(_FleetAvg(thr))
            .collect()
        )
        env.execute("solo-agg")
        return sorted(float(x) for x in h.items)

    got_a = sorted(float(x) for x in srv.output("a"))
    got_b = sorted(float(x) for x in srv.output("b"))
    assert got_a == pytest.approx(solo(a_lines, 100.0))
    assert got_b == pytest.approx(solo(b_lines, 200.0))
    # the thresholds actually landed (per-tenant, not global)
    assert all(100.0 <= x < 200.0 for x in got_a)
    assert all(x >= 200.0 for x in got_b)


def test_fleet_window_process_strips_namespace_and_tenant_field():
    """The host-evaluated process fn sees the BARE user key (tenant
    namespace stripped) and elements without the trailing tenant field;
    its collected output demuxes per tenant, matching a solo run."""
    from tpustream.tenancy.server import TENANT_SEP

    seen = []

    def fn(key, ctx, elements, out):
        seen.append((key, list(elements)))
        total = sum(e.f1 for e in elements)
        out.collect(Tuple2(key, total))

    plan = TenantPlan(
        parse=_kv_parse,
        build=lambda s, r: s.key_by(0).count_window(2).process(fn),
        rules=_kv_plan().rules,
        tenant_capacity=4,
    )
    srv = JobServer(plan, config=StreamConfig(batch_size=4))
    srv.add_tenant("a")
    srv.add_tenant("b")
    a_lines = [f"k{i % 2} {i}" for i in range(8)]
    b_lines = [f"k{i % 2} {10 * i}" for i in range(8)]
    for i in range(8):
        srv.ingest("a", [a_lines[i]])
        srv.ingest("b", [b_lines[i]])
    srv.run("fleet-process")

    assert seen, "process fn never fired"
    for key, elements in seen:
        assert TENANT_SEP not in key
        assert key in ("k0", "k1")
        for e in elements:
            assert isinstance(e, Tuple2), repr(e)

    def solo(lines):
        env = StreamExecutionEnvironment(StreamConfig(batch_size=4))
        h = (
            env.from_collection(lines)
            .map(_kv_parse)
            .key_by(0)
            .count_window(2)
            .process(fn)
            .collect()
        )
        env.execute("solo-process")
        return h.items

    assert reprs(srv.output("a")) == reprs(solo(a_lines))
    assert reprs(srv.output("b")) == reprs(solo(b_lines))


# ---------------------------------------------------------------------------
# unit surface: RuleSet tenancy / TenantPlan / JobServer guards
# ---------------------------------------------------------------------------
def test_ruleset_tenancy_vectors_and_growth():
    rules = RuleSet()
    rules.declare("t", 90.0, "f64")
    rules.enable_tenancy(3)  # rounds up to 4
    assert rules.tenant_capacity == 4
    rules.apply(RuleUpdate("t", 95.0, tenant=1))
    assert rules.tenant_value("t", 1) == 95.0
    assert rules.tenant_value("t", 0) == 90.0
    # a global update reaches every slot
    rules.apply(RuleUpdate("t", 70.0))
    assert [rules.tenant_value("t", s) for s in range(4)] == [70.0] * 4
    # addressing slot 5 doubles 4 -> 8, existing rows intact
    rules.apply(RuleUpdate("t", 99.0, tenant=5))
    assert rules.tenant_capacity == 8
    assert rules.tenant_value("t", 5) == 99.0
    assert rules.tenant_value("t", 1) == 70.0
    assert rules.version == 3
    leaves = rules.device_leaves()
    assert leaves["t"].shape == (8,)
    # values()/load() round-trip, including the vectors
    vals = rules.values()
    assert vals[TENANT_VALUES_KEY]["capacity"] == 8
    fresh = RuleSet()
    fresh.declare("t", 90.0, "f64")
    fresh.load(vals, rules.version)
    assert fresh.tenant_capacity == 8
    assert fresh.tenant_value("t", 5) == 99.0
    # reset reseeds defaults but KEEPS capacity (replay addresses slots)
    rules.reset()
    assert rules.version == 0
    assert rules.tenant_capacity == 8
    assert rules.tenant_value("t", 5) == 90.0


def test_ruleset_tenancy_guards():
    rules = RuleSet()
    rules.declare("t", 1.0)
    with pytest.raises(RuntimeError, match="enable_tenancy"):
        rules.ensure_tenant_slot(0)
    with pytest.raises(RuntimeError, match="tenancy is not enabled"):
        rules.apply(RuleUpdate("t", 2.0, tenant=0))
    with pytest.raises(ValueError, match=">= 1"):
        rules.enable_tenancy(0)
    rules.enable_tenancy(4)
    with pytest.raises(ValueError, match=">= 0"):
        rules.ensure_tenant_slot(-1)


def test_tenant_plan_shape_verification():
    plan = c6.make_plan()
    # the template itself verifies
    plan.verify(c6.build)
    # a different chain shape is rejected with both signatures named
    with pytest.raises(TenantShapeError):
        plan.verify(lambda s, r: s.map(lambda v: v))
    with pytest.raises(TenantShapeError):
        plan.verify(lambda s, r: s.filter(lambda v: v.f2 > 1).filter(
            lambda v: v.f2 > 2
        ))
    # add_tenant(build=...) runs the same check
    srv = JobServer(c6.make_plan(), config=StreamConfig())
    srv.add_tenant("ok", build=c6.build)
    with pytest.raises(TenantShapeError):
        srv.add_tenant("bad", build=lambda s, r: s.map(lambda v: v))


def test_key_field_inference_and_guards():
    # positional key_by is inferred
    assert _kv_plan().inferred_key_field() == 0
    # an explicit key_field wins
    plan = TenantPlan(
        parse=_kv_parse, build=_kv_build, rules=RuleSet(), key_field=1,
    )
    assert plan.inferred_key_field() == 1
    # a computed (callable) key can't be namespaced — explicit required
    bad = TenantPlan(
        parse=_kv_parse,
        build=lambda s, r: s.key_by(lambda v: v.f0).sum(1),
        rules=RuleSet(),
    )
    with pytest.raises(TenantShapeError, match="key_field"):
        bad.inferred_key_field()


def test_job_server_admission_guards():
    srv = JobServer(c6.make_plan(), config=StreamConfig())
    srv.add_tenant("a")
    with pytest.raises(ValueError, match="already admitted"):
        srv.add_tenant("a")
    with pytest.raises(KeyError, match="unknown tenant"):
        srv.ingest("nope", ["x"])
    with pytest.raises(KeyError, match="unknown tenant"):
        srv.update_tenant_rules("nope", {"threshold": 1.0})
    assert TenantQuota(max_records=2).admits(1)
    assert not TenantQuota(max_records=2).admits(2)
    assert TenantQuota().admits(10**9)  # unlimited


def test_package_exports_and_javacompat_aliases():
    import tpustream
    import tpustream.javacompat as jc

    for name in ("JobServer", "TenantPlan", "TenantQuota"):
        assert getattr(tpustream, name) is getattr(jc, name)
        assert name in tpustream.__all__
    srv = JobServer(c6.make_plan(), config=StreamConfig())
    assert srv.addTenant == srv.add_tenant
    assert srv.removeTenant == srv.remove_tenant
    assert srv.updateTenantRules == srv.update_tenant_rules


# ---------------------------------------------------------------------------
# slow tier: p=8 mesh parity + supervised fleet crash recovery
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_fleet_p8_matches_single_chip():
    """The mesh gate: the [T] rule vectors replicate (never shard), so
    the p=8 fleet demuxes identically to single-chip, per tenant."""
    def run_fleet(parallelism):
        srv = make_server(batch_size=8, parallelism=parallelism)
        for i, t in enumerate(["a", "b", "c"]):
            srv.add_tenant(t, rules={"threshold": 84.0 + 4 * i})
            srv.ingest(t, c6.tenant_lines(t, 16))
        srv.update_tenant_rules("b", {"threshold": 80.0})
        for t in ("a", "b", "c"):
            srv.ingest(t, c6.tenant_lines(t, 16, base=85.0))
        srv.run(f"fleet-p{parallelism}")
        return {t: reprs(srv.output(t)) for t in ("a", "b", "c")}

    single = run_fleet(1)
    mesh = run_fleet(8)
    assert mesh == single
    assert any(single[t] for t in single)  # non-trivial output


@pytest.mark.slow
def test_fleet_device_step_crash_recovers_supervised(tmp_path):
    """A device_step crash mid-fleet under supervision: restore from the
    v10 checkpoint (tenant table + rule vectors + sink rollback), replay
    — every tenant byte-identical to the uninterrupted fleet."""
    clean = _durable_fleet()
    clean.run("fleet-clean-slow")

    inj = FaultInjector(FaultPoint("device_step", at=3))
    srv = _durable_fleet(ckdir=tmp_path, injector=inj)
    srv.run("fleet-crash-slow", restart_strategy=fixed_delay(3, 0.0))
    assert inj.fired == 1
    for t in ("acme", "globex"):
        assert reprs(srv.output(t)) == reprs(clean.output(t)), t
