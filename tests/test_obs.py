"""Observability layer (tpustream/obs): registry scoping, histogram
percentiles vs a numpy oracle, Prometheus exposition goldens (hostile
label values included), the watermark-lag gauge and end-to-end latency
markers on a chapter-3 event-time job, the health engine's CRIT rule on
that job, the disabled-path overhead guard, snapshot/dump round trips
(and the dump CLI's --selftest smoke mode), and the DerivedKeyTable
snapshot-tear invariant."""

import json
import math
import threading

import numpy as np
import pytest

from tpustream import StreamExecutionEnvironment, Time, TimeCharacteristic
from tpustream.config import ObsConfig, StreamConfig
from tpustream.jobs.chapter3_bandwidth_eventtime import build as build_et
from tpustream.obs import (
    AlertRule,
    Histogram,
    MetricsRegistry,
    NULL_JOB_OBS,
    Snapshotter,
    StepTracer,
    job_snapshot,
    write_snapshot,
)
from tpustream.obs.dump import main as dump_main, render as dump_render
from tpustream.records import DerivedKeyTable
from tpustream.runtime.sources import ReplaySource


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_scoping_and_labels():
    reg = MetricsRegistry()
    job = reg.group(job="j1")
    op = job.group(operator="window")
    shard = op.group(shard=0)

    c1 = op.counter("operator_records_in")
    c2 = job.group(operator="window").counter("operator_records_in")
    assert c1 is c2  # idempotent by (name, labels)
    c1.inc(3)
    c2.inc(2)
    assert c1.value == 5

    # a different label set is a different series
    c3 = shard.counter("operator_records_in")
    assert c3 is not c1
    assert c3.value == 0
    assert c3.labels == {"job": "j1", "operator": "window", "shard": "0"}

    names = [(s.name, s.labels) for s in reg.series()]
    assert ("operator_records_in", {"job": "j1", "operator": "window"}) in names
    assert (
        "operator_records_in",
        {"job": "j1", "operator": "window", "shard": "0"},
    ) in names


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    g = reg.group(job="j")
    g.counter("x")
    with pytest.raises(TypeError):
        g.gauge("x")
    with pytest.raises(TypeError):
        g.histogram("x")


def test_gauge_set_fn_pull_and_exception_swallow():
    reg = MetricsRegistry()
    g = reg.group(job="j").gauge("depth")
    box = {"v": 7}
    g.set_fn(lambda: box["v"])
    assert g.value == 7
    box["v"] = 9
    assert g.value == 9

    def boom():
        raise RuntimeError("queue gone")

    g.set_fn(boom)
    # a dead callback is VISIBLE, not papered over: the read renders NaN
    # (a stale last-good value would hide the outage from dashboards)
    # and the failure is attributed in its own error counter
    assert math.isnan(g.value)
    assert math.isnan(g.value)  # stable across repeated scrapes
    errs = [
        s for s in reg.series()
        if s.name == "gauge_callback_errors"
    ]
    assert len(errs) == 1
    assert errs[0].labels == {"job": "j", "gauge": "depth"}
    assert errs[0].value == 2
    # NaN must survive prometheus rendering, not crash the formatter
    text = reg.to_prometheus_text()
    assert "tpustream_depth" in text and "NaN" in text


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(7)
    vals = rng.exponential(scale=3.0, size=257)
    h = Histogram("t", {})
    h.observe_many(vals.tolist())
    for q in (0, 10, 50, 90, 99, 100):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(vals, q)), rel=1e-12, abs=1e-12
        )
    assert h.count == len(vals)
    assert h.sum == pytest.approx(float(vals.sum()))


def test_histogram_ring_bound_keeps_exact_count_sum():
    h = Histogram("t", {}, max_samples=8)
    h.observe_many(range(100))
    assert h.count == 100
    assert h.sum == sum(range(100))
    assert len(h.samples) == 8
    assert sorted(h.samples) == list(range(92, 100))  # most recent retained


def test_prometheus_text_golden():
    # pinned clock: epoch aligned so exposition timestamps are exactly
    # sample-time * 1000 ms — the golden asserts the full line including
    # the per-series timestamp suffix
    reg = MetricsRegistry()
    reg.now = lambda: 1.5
    reg._epoch_wall = 0.0
    reg._epoch_perf = 0.0
    g = reg.group(job="demo", operator="window")
    g.counter("operator_records_in").inc(42)
    g.gauge("operator_inflight_steps").set(3)
    h = g.histogram("operator_step_time_s")
    # identical samples: every quantile is exactly 0.5, no float-repr
    # sensitivity in the golden (interpolation itself is pinned against
    # the numpy oracle above)
    h.observe_many([0.5, 0.5, 0.5, 0.5])
    assert reg.to_prometheus_text() == (
        '# TYPE tpustream_operator_inflight_steps gauge\n'
        'tpustream_operator_inflight_steps{job="demo",operator="window"} 3 1500\n'
        '# TYPE tpustream_operator_records_in counter\n'
        'tpustream_operator_records_in{job="demo",operator="window"} 42 1500\n'
        '# TYPE tpustream_operator_step_time_s summary\n'
        'tpustream_operator_step_time_s{job="demo",operator="window",quantile="0.5"} 0.5 1500\n'
        'tpustream_operator_step_time_s{job="demo",operator="window",quantile="0.9"} 0.5 1500\n'
        'tpustream_operator_step_time_s{job="demo",operator="window",quantile="0.99"} 0.5 1500\n'
        'tpustream_operator_step_time_s_sum{job="demo",operator="window"} 2 1500\n'
        'tpustream_operator_step_time_s_count{job="demo",operator="window"} 4 1500\n'
    )
    # back-to-back renders are byte-identical — rendering never advances
    # any sample clock
    assert reg.to_prometheus_text() == reg.to_prometheus_text()


def test_prometheus_text_escapes_hostile_label_values():
    """Exposition golden for a label value containing every character
    the text format escapes: backslash, double quote, and newline."""
    reg = MetricsRegistry()
    reg.now = lambda: 2.0
    reg._epoch_wall = 0.0
    reg._epoch_perf = 0.0
    reg.group(job="j", operator='he"llo\\wo\nrld').counter(
        "operator_records_in"
    ).inc(1)
    assert reg.to_prometheus_text() == (
        '# TYPE tpustream_operator_records_in counter\n'
        'tpustream_operator_records_in'
        '{job="j",operator="he\\"llo\\\\wo\\nrld"} 1 2000\n'
    )


# ---------------------------------------------------------------------------
# tracing + snapshot plumbing
# ---------------------------------------------------------------------------


def test_tracer_ring_overwrite_and_snapshot():
    tr = StepTracer(capacity=4)
    for i in range(6):
        with tr.span("dispatch", step=i, operator="window"):
            pass
    snap = tr.snapshot()
    assert snap["total_spans"] == 6
    assert snap["dropped_spans"] == 2
    assert len(snap["events"]) == 4
    assert [e["step"] for e in snap["events"]] == [2, 3, 4, 5]  # oldest dropped
    assert all(e["kind"] == "dispatch" for e in snap["events"])
    assert all(e["dur_s"] >= 0 for e in snap["events"])


def test_snapshotter_and_write_snapshot_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.group(job="j").counter("batches").inc(5)
    tr = StepTracer(capacity=8)
    with tr.span("fetch", step=1, operator="window"):
        pass
    jsonl = tmp_path / "series.jsonl"
    snapper = Snapshotter(
        reg, tr, interval_s=1e9, jsonl_path=str(jsonl), meta={"job": "j"}
    )
    assert snapper.enabled
    assert snapper.maybe_snapshot() is None  # interval not yet elapsed
    snap = snapper.take()
    assert snap["version"] == 1
    assert snap["meta"]["job"] == "j"
    assert snap["trace"]["total_spans"] == 1
    # JSONL line parses back to the same snapshot
    assert json.loads(jsonl.read_text()) == json.loads(
        json.dumps(snap, sort_keys=True)
    )

    path = tmp_path / "snap.json"
    write_snapshot(str(path), job_snapshot(reg, tr, meta={"job": "j"}))
    loaded = json.loads(path.read_text())
    assert loaded["metrics"]["series"][0]["name"] == "batches"
    assert "tpustream_batches" in loaded["prometheus"]


def test_dump_render_and_cli(tmp_path, capsys):
    reg = MetricsRegistry()
    g = reg.group(job="j", operator="window")
    g.counter("operator_records_in").inc(11)
    g.histogram("operator_step_time_s").observe_many([0.5, 1.5])
    tr = StepTracer(capacity=8)
    with tr.span("emit", step=1, operator="window"):
        pass
    path = tmp_path / "snap.json"
    write_snapshot(str(path), job_snapshot(reg, tr, meta={"job": "j"}))

    text = dump_render(json.loads(path.read_text()))
    assert "operator_records_in" in text
    assert "HISTOGRAM" in text
    assert "emit" in text

    assert dump_main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "operator_records_in" in out
    assert dump_main([str(path), "--prom"]) == 0
    assert "tpustream_operator_records_in" in capsys.readouterr().out


def test_dump_selftest_smoke(capsys):
    """`python -m tpustream.obs.dump --selftest` is the CI smoke mode:
    canned registry -> snapshot -> render -> Prometheus -> health ->
    flight dump, every check must hold — and the check count is pinned
    so a silently-dropped check block fails loudly here."""
    import re

    assert dump_main(["--selftest"]) == 0
    out = capsys.readouterr().out
    assert "FAIL" not in out
    m = re.search(r"selftest ok \((\d+) checks\)", out)
    assert m, out
    assert int(m.group(1)) == 133
    # the multi-tenant series checks are part of the suite
    assert "ok: prometheus carries the per-tenant labels" in out
    # ... and the sharded-ingestion lane series
    assert "ok: prometheus carries the per-lane ingest counters" in out
    assert "ok: prometheus carries the fleet gauges" in out
    # ... including the per-tenant SLO / budget-burn surface
    assert "ok: health carries the per-tenant SLO rule states" in out
    assert "ok: breaching tenant burns its error budget" in out
    assert "ok: tenants render carries the SLO verdicts" in out
    # the pre-flight analysis counter checks are part of the suite
    assert "ok: prometheus carries the per-code analysis findings" in out
    # ... including the schema-inference / checkpoint-audit codes
    assert "ok: prometheus carries the schema and audit finding codes" in out
    # the lane supervision / self-healing surface is part of the suite
    assert "ok: prometheus carries the lane supervision series" in out
    assert "ok: flight keeps the degradation ladder in order" in out
    assert "ok: flight keeps the checkpoint_audit breadcrumb" in out
    # the unified Perfetto timeline checks are part of the suite
    assert "ok: record lineage spans source->sink" in out
    assert "ok: flight events export as instants" in out
    assert "ok: tracer ring overflow counts drops" in out
    assert "ok: /trace.json serves the timeline" in out
    # the conservation-ledger checks are part of the suite
    assert "ok: balanced edges evaluate to zero residuals" in out
    assert "ok: hand-tampered sink trips the contents edge" in out
    assert "ok: forged anchor flags a restore digest mismatch" in out
    assert "ok: ledger.json round-trips the state" in out
    # the checkpoint-plane renderer checks are part of the suite
    assert "ok: incremental delta counts only fresh chunks" in out
    assert "ok: chunk store separates referenced from orphaned" in out
    assert "ok: interrupted GC mark is surfaced" in out


# ---------------------------------------------------------------------------
# end-to-end: chapter-3 event-time job with obs enabled / disabled
# ---------------------------------------------------------------------------

# 240 lines / 16-row batches = 15 source polls, so the per-poll latency
# marker stamping below yields >= 10 markers through the pipeline
ET_LINES = [
    f"2020-01-01T00:{m:02d}:{s:02d} ch{(m + s) % 3} 999999999"
    for m in range(4)
    for s in range(60)
]


_CH3_CACHE = {}


def _run_ch3(enabled: bool):
    """One jitted job run per obs setting, shared across the e2e tests
    (the suite is compile-bound on the 1-core driver host). The enabled
    run carries the full tentpole surface: latency markers on every
    source poll and a watermark-lag health rule that the job's 1-minute
    bounded-out-of-orderness delay is guaranteed to breach."""
    if enabled in _CH3_CACHE:
        return _CH3_CACHE[enabled]
    obs = ObsConfig(
        enabled=enabled,
        latency_marker_interval_ms=1e-6 if enabled else 0.0,
        health_rules=(
            AlertRule(name="lag_crit", metric="watermark_lag_ms",
                      op=">", value=30_000, severity="crit"),
        ) if enabled else (),
    )
    cfg = StreamConfig(batch_size=16, key_capacity=64, obs=obs)
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    build_et(
        env,
        env.add_source(ReplaySource(ET_LINES)),
        size=Time.minutes(5),
        slide=Time.seconds(5),
        delay=Time.minutes(1),
    ).collect()
    env.execute("obs-e2e")
    _CH3_CACHE[enabled] = env.metrics
    return env.metrics


def test_eventtime_job_obs_enabled():
    m = _run_ch3(enabled=True)
    snap = m.obs_snapshot()
    series = {(s["name"], s["labels"].get("operator")): s for s in
              snap["metrics"]["series"]}

    # nonzero watermark-lag gauge (bounded OOO delay = 1 min)
    lag = series[("watermark_lag_ms", None)]
    assert lag["type"] == "gauge"
    assert lag["value"] == 60_000
    assert series[("watermark_ms", None)]["value"] > 0

    # per-operator counters from the window runner
    win_in = series[("operator_records_in", "window")]
    assert win_in["value"] == len(ET_LINES)
    assert series[("operator_steps", "window")]["value"] >= 1
    assert ("operator_step_time_s", "window") in series

    # step-span trace covers the batch lifecycle
    kinds = {e["kind"] for e in snap["trace"]["events"]}
    assert {"parse", "pack", "dispatch", "fetch", "emit"} <= kinds

    # both exposition forms agree on the lag gauge
    assert "tpustream_watermark_lag_ms" in m.to_prometheus_text()
    assert "tpustream_watermark_lag_ms" in snap["prometheus"]


def test_eventtime_job_latency_markers_end_to_end():
    """Markers stamped at the source ride the full pack/dispatch/fetch
    path and land in per-edge and per-sink e2e histograms — true
    source->sink latency, measured without any per-record work."""
    m = _run_ch3(enabled=True)
    snap = m.obs_snapshot()
    series = {(s["name"], s["labels"].get("operator")): s for s in
              snap["metrics"]["series"]}

    emitted = series[("latency_markers_emitted", None)]["value"]
    assert emitted >= 10  # one per source poll (240 lines / 16-row batches)

    for name in ("operator_e2e_latency_ms", "operator_sink0_e2e_latency_ms"):
        h = series[(name, "window")]
        assert h["type"] == "histogram"
        # every marker settles: none lost in the pipelined in-flight
        # window or the end-of-stream drain
        assert h["value"]["count"] == emitted
        assert h["value"]["p50"] > 0
        assert h["value"]["p99"] >= h["value"]["p50"] > 0


def test_eventtime_job_health_rule_goes_crit():
    """The watermark-lag rule breaches on the job's constant 60 s lag
    (1-minute bounded out-of-orderness) and reports CRIT in the
    embedded health section with an explanatory reason."""
    m = _run_ch3(enabled=True)
    snap = m.obs_snapshot()
    health = snap["health"]
    assert health["level"] == "crit"
    (rule,) = [r for r in health["rules"] if r["rule"] == "lag_crit"]
    assert rule["level"] == "crit"
    assert rule["value"] == 60_000
    assert "watermark_lag_ms > 30000" in rule["reason"]
    # the rule's own state is a scrapeable gauge (0=ok 1=warn 2=crit)
    states = {s["labels"].get("rule"): s["value"]
              for s in snap["metrics"]["series"]
              if s["name"] == "health_rule_state"}
    assert states["lag_crit"] == 2


def test_eventtime_job_obs_disabled_no_marker_injection():
    """obs off => the stamper is never installed: no marker series, no
    marker objects, no e2e histograms."""
    m = _run_ch3(enabled=False)
    names = {s["name"] for s in m.obs_snapshot()["metrics"]["series"]}
    assert "latency_markers_emitted" not in names
    assert not any("e2e_latency" in n for n in names)


def test_eventtime_job_obs_disabled_no_instrument_updates():
    m = _run_ch3(enabled=False)
    assert m.job_obs is NULL_JOB_OBS
    assert m.job_obs.tracer.total_spans == 0
    names = {s["name"] for s in m.obs_snapshot()["metrics"]["series"]}
    assert not any(n.startswith("operator_") for n in names)
    assert "watermark_lag_ms" not in names


def test_summary_keys_unchanged_by_obs():
    disabled = _run_ch3(enabled=False).summary()
    enabled = _run_ch3(enabled=True).summary()
    assert set(enabled) == set(disabled)
    assert enabled["records_in"] == disabled["records_in"] == len(ET_LINES)


# ---------------------------------------------------------------------------
# satellite: DerivedKeyTable snapshot tear
# ---------------------------------------------------------------------------


def test_derived_key_table_snapshot_tear():
    """state_dict must never pair a string with a missing original:
    intern_value appends the canonical string FIRST, so a concurrent
    snapshot (checkpoint under parse-ahead) can observe len(_to_str) >
    len(_originals) mid-intern. The capture-then-truncate order pins
    len(strings) == len(originals) with consistent pairs."""
    t = DerivedKeyTable()
    done = threading.Event()
    err = []
    N = 20_000

    def hammer():
        for i in range(N):
            t.intern_value(f"k{i}")
        done.set()

    def check():
        try:
            checks = 0
            while not done.is_set() or checks < 10:
                d = t.state_dict()
                assert len(d["strings"]) == len(d["originals"])
                for s, o in zip(d["strings"], d["originals"]):
                    if o is not None:  # slot 0 is the reserved placeholder
                        assert s == f"{type(o).__name__}:{o!r}"
                checks += 1
        except BaseException as e:  # pragma: no cover
            err.append(e)

    w = threading.Thread(target=hammer)
    r = threading.Thread(target=check)
    w.start()
    r.start()
    w.join()
    r.join()
    assert not err
    d = t.state_dict()
    assert len(d["originals"]) == N + 1  # all keys + placeholder
