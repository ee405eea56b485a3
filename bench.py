#!/usr/bin/env python
"""Benchmark harness: all five BASELINE.json configs.

Measures the BASELINE.json north-star metric — sustained events/sec/chip
on the flagship job (5-min/5-s sliding windows, 1M keys, bounded
out-of-orderness watermarks, out-of-order arrivals, Mbps alert filter) —
plus p99 ingest->alert latency, native parse throughput, the ch2 rolling
and ch1/ch3 configs, and the FULL execute_job path (raw-bytes source ->
native parse -> H2D -> device -> alert sink). The per-stage rates are
reported so the full-path numbers can be reconstructed from them.

Methodology: the stream is generated ON DEVICE at a fixed intrinsic
event-time rate (SIM_RATE = the 10M ev/s target), so pane advances and
slide-boundary window fires happen at exactly the cadence a real
10M ev/s stream induces. Steps are chained CHUNK at a time inside one
jitted ``lax.scan`` (state donated, alert/late tallies carried on
device), so a timing interval pays one host->device round trip per
CHUNK steps rather than per step, and each interval ends in a host
fetch. The flagship config uses the
32-bit accumulator fast path (StreamConfig.acc_dtype="int32"):
commutative combiners become non-unique 32-bit scatter-reduces, while
window sums still compose in int64 at fire.

Prints ONE JSON line: metric/value/unit/vs_baseline. Detail -> stderr.
"""

import collections
import hashlib
import json
import sys
import time

import numpy as np

#: record format version: 2 added the env fingerprint header and the
#: folded stderr tail (detail.stderr_tail); pre-2 records have neither
BENCH_SCHEMA = 2

# last N stderr lines, folded into the record as detail.stderr_tail so
# a round's narrative survives without a committed bench_stderr.txt
_LOG_TAIL = collections.deque(maxlen=60)


def log(*a):
    _LOG_TAIL.append(" ".join(str(x) for x in a))
    print(*a, file=sys.stderr, flush=True)


# phases that raised: the run still finishes and prints its record, then
# exits non-zero (a failed phase is a failure, not a skip)
_FAILED_PHASES: list = []


def phase_failed(name, e):
    _FAILED_PHASES.append(name)
    log(f"phase {name} FAILED: {type(e).__name__}: {e}")


B = 1 << 19            # 524288 records/step: batch-size sweep (full
                       # bench runs) — 131072: 25.9M ev/s @ p99 24 ms;
                       # 262144: 33.0M @ 40 ms; 524288: 38.2M @ 72 ms.
                       # The scatter's fixed cost amortizes sublinearly;
                       # 524288 maximizes throughput while p99 (residency
                       # 52 ms + 20 ms firing step) stays under the
                       # 100 ms budget
K = 1 << 20            # 1M keys (BASELINE.json config 5)
SIM_RATE = 10_000_000  # intrinsic stream rate: fires at real cadence
BASE_MS = 1_566_957_600_000
TARGET = 10_000_000    # north star: >= 10M events/s/chip
CHUNK = 200            # steps per jitted scan dispatch


class _GenBytesSource:
    """Pre-rendered fixed-width line buffers (BL lines = one STREAM
    second), with the ISO time field patched per emission (numpy,
    ~1 ms/buffer). Records wall-clock marks so the caller can time the
    steady segment.

    Paced mode (``rate``) emits ARRIVAL-SIZED buffers: with ``fill_ms``
    set, each emission carries ~rate*fill_ms/1000 lines — what a real
    socket source hands the executor after one max_batch_delay_ms fill
    window at that arrival rate. (Round-4 paced runs shipped full
    65536-line buffers even at 0.2M ev/s — 330 ms of stream per batch —
    which inflated paced p99 by several batch times; VERDICT r4 next
    #1.) The executor is told the matching batch_size so the compiled
    step matches the arrival shape."""

    def __init__(self, template, time_cols, n_buffers, warm_buffers,
                 lines_per_buffer, start_proc_ms, rate=None, fill_ms=None):
        self.template = template          # [BL, LINE_W] uint8
        self.time_cols = time_cols        # (hh, mm, ss) column indices
        self.n_buffers = n_buffers
        self.warm = warm_buffers
        self.bl = lines_per_buffer
        self.start_proc_ms = start_proc_ms
        self.rate = rate                  # records/s pacing (None = flood)
        self.fill_ms = fill_ms            # arrival-batch fill target
        self.t_steady_start = None
        self.t_end = None
        self.max_behind_s = 0.0           # worst schedule slip when paced
        self.rows_per_batch = self.batch_rows()

    def batch_rows(self) -> int:
        """Lines per emission: the full render buffer when flooding, a
        pow2 arrival-sized slice when paced with a fill target."""
        if not (self.rate and self.fill_ms):
            return self.bl
        want = max(1, int(self.rate * self.fill_ms / 1e3))
        rows = 1 << (want - 1).bit_length()   # pow2: few compile shapes
        return int(min(self.bl, max(4096, rows)))

    def batches(self, batch_size, max_delay_ms):
        import numpy as np

        from tpustream.runtime.sources import SourceBatch

        hh_c, mm_c, ss_c = self.time_cols
        arr = self.template
        total = self.n_buffers * self.bl
        warm_lines = self.warm * self.bl
        rows = self.rows_per_batch
        t_sched0 = None
        pos = 0
        while pos < total:
            sec = pos // self.bl
            lo = pos % self.bl
            # never cross a stream-second boundary in one emission
            n = min(rows, total - pos, self.bl - lo)
            sl = arr[lo : lo + n]
            ss, mm, hh = sec % 60, (sec // 60) % 60, 10 + sec // 3600
            for col, v in ((hh_c, hh), (mm_c, mm), (ss_c, ss)):
                sl[:, col] = ord("0") + v // 10
                sl[:, col + 1] = ord("0") + v % 10
            if self.rate:
                # RELATIVE rate control: each buffer is released one
                # inter-buffer interval after the previous release, and
                # the schedule re-anchors when the pipeline falls behind
                # (no debt accumulation — a one-off stall like the first
                # jit compile must not turn the rest of the run into a
                # flood). The source is pull-driven, so a slow pipeline
                # shows up as schedule slip (max_behind_s) and a lower
                # achieved steady rate — explicit backpressure, not an
                # unbounded queue.
                now = time.perf_counter()
                if t_sched0 is not None:
                    if now < t_sched0:
                        time.sleep(t_sched0 - now)
                        now = t_sched0
                    elif self.t_steady_start is not None:
                        # STEADY-state slip only: the warm segment's
                        # one-off jit compile is not backpressure
                        self.max_behind_s = max(
                            self.max_behind_s, now - t_sched0
                        )
                t_sched0 = now + n / self.rate
            if self.t_steady_start is None and pos >= warm_lines:
                self.t_steady_start = time.perf_counter()
                self._steady_base = pos
            yield SourceBatch(
                [],
                np.full(
                    n, self.start_proc_ms + pos * 1000 // self.bl, np.int64
                ),
                raw=sl.tobytes(),
                n_raw=n,
            )
            pos += n
        self.t_end = time.perf_counter()
        yield SourceBatch([], np.empty(0, np.int64), final=True)

    def steady_rate(self):
        n = self.n_buffers * self.bl - self._steady_base
        return n / (self.t_end - self.t_steady_start)


def _render_flagship_lines(bl, n_keys):
    """[BL, 46] uint8: '2019-08-28T10:00:00 www.XXXXXX.com FFFFFFFFFF\\n'
    — ~1/128 channels alert (flow 1); the rest carry 1e9 (127 Mbps,
    filtered). Returns (template, (hh, mm, ss) col indices)."""
    line = b"2019-08-28T10:00:00 www.000000.com 1000000000\n"
    arr = np.tile(np.frombuffer(line, np.uint8), (bl, 1)).copy()
    g = np.arange(bl, dtype=np.int64)
    h = g * 2654435761
    keys = ((h ^ (h >> 29)) % n_keys).astype(np.int64)
    for d in range(6):
        arr[:, 24 + d] = ord("0") + (keys // 10 ** (5 - d)) % 10
    alerting = (keys % 128) == 0
    arr[alerting, 35:45] = np.frombuffer(b"0000000001", np.uint8)
    return arr, (11, 14, 17)


def _render_ch1_lines(bl):
    """[BL, 29] uint8: '1563450000 h000000 cpu0 50.5\\n' — ~1/128 of
    usages exceed the >90 threshold."""
    line = b"1563450000 h000000 cpu0 50.5\n"
    arr = np.tile(np.frombuffer(line, np.uint8), (bl, 1)).copy()
    g = np.arange(bl, dtype=np.int64)
    h = g * 2654435761
    hosts = ((h ^ (h >> 31)) % 256).astype(np.int64)
    for d in range(6):
        arr[:, 12 + d] = ord("0") + (hosts // 10 ** (5 - d)) % 10
    arr[:, 22] = ord("0") + (g % 4).astype(np.uint8)  # cpu0..cpu3
    alerting = (g % 128) == 0
    arr[alerting, 24:28] = np.frombuffer(b"91.5", np.uint8)
    return arr, None


def _lat_result(src, m, alerts):
    """Shared paced/flood result record with stage attribution: p50/p99
    measured from batch close -> alert dispatch; fill_ms is the batch's
    arrival span (a record waits at most that long before its batch
    closes), so the FULL-path p99 a deployment sees is fill + measured."""
    lat = np.array(m.emit_latencies_s) * 1e3
    p99 = float(np.percentile(lat, 99)) if lat.size else None
    p95 = float(np.percentile(lat, 95)) if lat.size else None
    p50 = float(np.percentile(lat, 50)) if lat.size else None
    fill_ms = (
        src.rows_per_batch / src.rate * 1e3 if src.rate else 0.0
    )
    host = np.array(m.host_times_s[3:]) * 1e3
    steps = np.array(m.step_times_s) * 1e3
    return dict(
        rate=src.steady_rate(), p99_ms=p99, p50_ms=p50, alerts=len(alerts),
        behind_s=src.max_behind_s, summary=m.summary(),
        rows_per_batch=src.rows_per_batch,
        fill_ms=fill_ms,
        p99_full_ms=(fill_ms + p99) if p99 is not None else None,
        p95_full_ms=(fill_ms + p95) if p95 is not None else None,
        p50_full_ms=(fill_ms + p50) if p50 is not None else None,
        host_ms_med=float(np.median(host)) if host.size else None,
        # fetch entries dominate the upper tail of step_times under the
        # paced sync path (submit entries are ~0): p90 ~= count-fetch +
        # emission-fetch wait per firing batch
        step_ms_p90=float(np.percentile(steps, 90)) if steps.size else None,
    )


def full_path_flagship(rate=None, nbuf=200, warm=80, fill_ms=None,
                       fetch_group=1, async_depth=4, delay_s=60):
    """Config 4/5 through execute_job: raw bytes -> native ISO parse +
    intern -> H2D -> sliding event-time windows -> Mbps alert sink.
    Windows scaled to (5 s, 1 s) so the 1-min watermark delay is
    crossable in-bench; per-event device work is identical (pane ring).
    ``rate`` paces the source (records/s); None floods. ``fill_ms``
    sizes paced arrival batches; ``fetch_group`` amortizes the per-step
    count-fetch RTT under flood (StreamConfig.fetch_group)."""
    from tpustream import StreamExecutionEnvironment, Time, TimeCharacteristic
    from tpustream.config import StreamConfig
    from tpustream.jobs.chapter3_bandwidth_eventtime import build

    BL, NKEY = 1 << 16, 1 << 20
    tpl, tcols = _render_flagship_lines(BL, NKEY)
    src = _GenBytesSource(
        tpl, tcols, nbuf, warm, BL, 1_566_957_600_000, rate=rate,
        fill_ms=fill_ms,
    )
    cfg = StreamConfig(
        batch_size=src.rows_per_batch,
        key_capacity=NKEY,
        alert_capacity=1 << 16,
        async_depth=async_depth,
        fetch_group=fetch_group,
        max_batch_delay_ms=0.0,
        # flood: overlap parse with the link (paced runs keep the
        # inline host stage — latency attribution stays exact)
        parse_ahead=0 if rate else 2,
    )
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    alerts = []
    build(
        env, env.add_source(src), size=Time.seconds(5), slide=Time.seconds(1),
        # paced rungs shrink the watermark delay so the event-time ramp
        # (delay + size of stream before the first fire) costs seconds,
        # not minutes of wall clock at low rates; per-event device work
        # is identical
        delay=Time.seconds(delay_s),
    ).add_sink(lambda r: alerts.append(r))
    env.execute("flagship-full-path")
    return _lat_result(src, env.metrics, alerts)


def full_path_ch1(rate=None, nbuf=65, warm=5, fill_ms=None,
                  fetch_group=1, async_depth=4):
    """Config 1 through execute_job: the stateless threshold-alert job
    (parse -> filter usage>90 -> sink)."""
    from tpustream import StreamExecutionEnvironment
    from tpustream.config import StreamConfig
    from tpustream.jobs.chapter1_threshold import build

    BL = 1 << 16
    tpl, _ = _render_ch1_lines(BL)
    src = _GenBytesSource(
        tpl, (1, 4, 7), nbuf, warm, BL, 1_563_450_000_000, rate=rate,
        fill_ms=fill_ms,
    )
    # time patch writes into the numeric ts field (unused by the job)
    cfg = StreamConfig(
        batch_size=src.rows_per_batch, async_depth=async_depth,
        fetch_group=fetch_group, max_batch_delay_ms=0.0,
        parse_ahead=0 if rate else 2,
    )
    env = StreamExecutionEnvironment(cfg)
    alerts = []
    build(env, env.add_source(src)).add_sink(lambda r: alerts.append(r))
    env.execute("Window WordCount")
    return _lat_result(src, env.metrics, alerts)


def obs_snapshot_probe():
    """Phase O: run a tiny obs-enabled chapter3 event-time job and
    return its metrics/trace snapshot for the JSON tail.  The job is
    deliberately small (a few dozen replayed lines, 16-row batches) —
    this phase documents the observability surface (per-operator
    counters, watermark-lag gauge, step spans, end-to-end latency
    markers, and the self-monitoring health engine), not a rate."""
    from tpustream import StreamExecutionEnvironment, Time, TimeCharacteristic
    from tpustream.config import ObsConfig, StreamConfig
    from tpustream.jobs.chapter3_bandwidth_eventtime import build
    from tpustream.obs import AlertRule
    from tpustream.runtime.sources import ReplaySource

    lines = [
        f"2020-01-01T00:{m:02d}:{s:02d} ch{(m * 12 + s) % 3} 999999999"
        for m in range(3)
        for s in range(0, 60, 5)
    ]
    cfg = StreamConfig(
        batch_size=16,
        key_capacity=64,
        obs=ObsConfig(
            enabled=True,
            # one marker per source poll: the probe exists to show the
            # e2e-latency surface, so stamp aggressively
            latency_marker_interval_ms=0.001,
            health_rules=(
                AlertRule(
                    name="lag_crit", metric="watermark_lag_ms",
                    op=">", value=30_000.0, severity="crit",
                ),
            ),
        ),
    )
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    out = build(
        env,
        env.add_source(ReplaySource(lines)),
        size=Time.minutes(5),
        slide=Time.seconds(5),
        delay=Time.minutes(1),
    ).collect()
    env.execute("obs-probe")
    return env.metrics.obs_snapshot(
        meta={"phase": "O", "lines": len(lines), "collected": len(out.items)}
    )


def trace_overhead_probe():
    """Phase O2: record flight-path tracing cost + parity (ISSUE 16).
    Runs the phase-O tiny chapter3 job twice — obs-on with markers but
    no record tracing, then the same job with trace_sample_rate=0.01
    (the documented 1% production setting) — and reports the wall-clock
    overhead of the tracing leg, whether the collected rows stayed
    byte-identical (markers and traces are control events, never
    records), and a trimmed unified timeline so r08's flamecharts ship
    with the numbers."""
    from tpustream import StreamExecutionEnvironment, Time, TimeCharacteristic
    from tpustream.config import ObsConfig, StreamConfig
    from tpustream.jobs.chapter3_bandwidth_eventtime import build
    from tpustream.obs import timeline_from_snapshot
    from tpustream.runtime.sources import ReplaySource

    lines = [
        f"2020-01-01T00:{m:02d}:{s:02d} ch{(m * 12 + s) % 3} "
        f"{100 + (m * 60 + s) % 997}"
        for m in range(3)
        for s in range(0, 60, 5)
    ]

    def run(rate):
        cfg = StreamConfig(
            batch_size=16,
            key_capacity=64,
            obs=ObsConfig(
                enabled=True,
                latency_marker_interval_ms=0.001,
                trace_sample_rate=rate,
            ),
        )
        env = StreamExecutionEnvironment(cfg)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        out = build(
            env,
            env.add_source(ReplaySource(lines)),
            size=Time.minutes(5),
            slide=Time.seconds(5),
            delay=Time.minutes(1),
        ).collect()
        t0 = time.perf_counter()
        env.execute("trace-probe")
        wall = time.perf_counter() - t0
        return wall, out.items, env.metrics

    base_wall, base_rows, _ = run(0.0)
    trace_wall, trace_rows, m = run(0.01)
    snap = m.obs_snapshot(meta={"phase": "O2"})
    timeline = timeline_from_snapshot(snap) or {}
    events = timeline.get("traceEvents", [])
    overhead = (
        (trace_wall - base_wall) / base_wall * 100.0 if base_wall else 0.0
    )
    return {
        "sample_rate": 0.01,
        "base_wall_s": round(base_wall, 6),
        "trace_wall_s": round(trace_wall, 6),
        "overhead_pct": round(overhead, 3),
        "sink_digest_base": _sink_digest(base_rows),
        "sink_digest_traced": _sink_digest(trace_rows),
        "output_identical": _sink_digest(base_rows) == _sink_digest(trace_rows),
        "record_traces_total": snap.get("record_traces_total", 0),
        "timeline_meta": timeline.get("meta", {}),
        # the timeline itself, trimmed so the JSON tail stays readable
        "timeline_events_head": events[:64],
        "timeline_events_total": len(events),
    }


def ledger_overhead_probe():
    """Phase O3: conservation-ledger cost + parity (ISSUE 18). Runs the
    phase-O tiny chapter3 job twice — obs-on with the ledger explicitly
    off, then the same job with the ledger on (auto + digests) — and
    reports the wall-clock overhead of the accounting leg, whether the
    collected rows stayed byte-identical (the ledger observes the emit
    path, it never touches a record), and the per-edge residual summary
    with the digest anchors, so every round carries the conservation
    proof next to its rates."""
    from tpustream import StreamExecutionEnvironment, Time, TimeCharacteristic
    from tpustream.config import ObsConfig, StreamConfig
    from tpustream.jobs.chapter3_bandwidth_eventtime import build
    from tpustream.runtime.sources import ReplaySource

    lines = [
        f"2020-01-01T00:{m:02d}:{s:02d} ch{(m * 12 + s) % 3} "
        f"{100 + (m * 60 + s) % 997}"
        for m in range(3)
        for s in range(0, 60, 5)
    ]

    def run(ledger):
        cfg = StreamConfig(
            batch_size=16,
            key_capacity=64,
            obs=ObsConfig(enabled=True, ledger=ledger),
        )
        env = StreamExecutionEnvironment(cfg)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        out = build(
            env,
            env.add_source(ReplaySource(lines)),
            size=Time.minutes(5),
            slide=Time.seconds(5),
            delay=Time.minutes(1),
        ).collect()
        t0 = time.perf_counter()
        env.execute("ledger-probe")
        wall = time.perf_counter() - t0
        return wall, out.items, env.metrics

    base_wall, base_rows, _ = run(False)
    led_wall, led_rows, m = run(None)  # None = auto: on with obs on
    snap = m.obs_snapshot(meta={"phase": "O3"})
    led = snap.get("ledger") or {}
    residuals = {
        e["edge"]: e.get("residual") for e in led.get("edges", [])
    }
    evaluated = [r for r in residuals.values() if r is not None]
    overhead = (
        (led_wall - base_wall) / base_wall * 100.0 if base_wall else 0.0
    )
    return {
        "base_wall_s": round(base_wall, 6),
        "ledger_wall_s": round(led_wall, 6),
        "overhead_pct": round(overhead, 3),
        "sink_digest_base": _sink_digest(base_rows),
        "sink_digest_ledger": _sink_digest(led_rows),
        "output_identical": _sink_digest(base_rows) == _sink_digest(led_rows),
        "edges_evaluated": len(evaluated),
        "residuals": residuals,
        "all_residuals_zero": bool(evaluated)
        and all(r == 0 for r in evaluated),
        "violations": led.get("violations", {}).get("total", 0),
        "anchors": led.get("anchors", {}),
        "ticks": led.get("ticks", 0),
    }


def recovery_probe():
    """Phase R: supervised-execution probe (docs/recovery.md). Runs a
    small checkpointed chapter2 job twice — clean, then with an injected
    mid-stream device fault under fixed_delay — and reports what the
    supervisor did: restarts taken, batches replayed, recovery wall
    clock, checkpoint save cost, and whether the recovered output is
    byte-identical to the clean run (the exactly-once contract). Like
    phase O this documents a surface, not a rate."""
    import tempfile

    from tpustream import StreamExecutionEnvironment
    from tpustream.config import ObsConfig, StreamConfig
    from tpustream.jobs.chapter2_max import build
    from tpustream.runtime.sources import ReplaySource
    from tpustream.runtime.supervisor import fixed_delay
    from tpustream.testing import FaultInjector, FaultPoint

    lines = [
        f"15634520{j:02d} 10.8.22.{j % 5} cpu{j % 3} {40 + (j * 13) % 60}.5"
        for j in range(24)
    ]

    def run(cfg, injector=None, supervised=False):
        if injector is not None:
            cfg = injector.install(cfg)
        env = StreamExecutionEnvironment(cfg)
        if supervised:
            env.set_restart_strategy(fixed_delay(3, 0.0))
        handle = build(env, env.add_source(ReplaySource(lines))).collect()
        env.execute("recovery-probe")
        return env, handle.items

    _, want = run(StreamConfig(batch_size=4, key_capacity=64))
    with tempfile.TemporaryDirectory() as ckdir:
        inj = FaultInjector(FaultPoint("device_step", at=3))
        env, got = run(
            StreamConfig(
                batch_size=4,
                key_capacity=64,
                checkpoint_dir=ckdir,
                checkpoint_interval_batches=1,
                obs=ObsConfig(enabled=True),
            ),
            injector=inj,
            supervised=True,
        )
    series = env.metrics.obs_snapshot()["metrics"]["series"]

    def total(name, field=None):
        vals = [
            s["value"][field] if field else s["value"]
            for s in series
            if s["name"].endswith(name)
        ]
        return sum(vals) if vals else None

    return dict(
        faults_fired=inj.fired,
        restarts=total("job_restarts_total"),
        replay_batches=total("recovery_replay_batches"),
        recovery_wall_ms=total("recovery_wall_ms", "p50"),
        checkpoint_save_ms_p50=total("checkpoint_save_ms", "p50"),
        checkpoint_bytes_p50=total("checkpoint_bytes", "p50"),
        output_intact=got == want,
    )


def checkpoint_overhead_probe(sizes=(("small", 64), ("large", 1024))):
    """Phase C2: checkpoint-plane cost probe (docs/recovery.md "The
    checkpoint plane"). The same checkpointed chapter2 job runs under
    both plane postures — synchronous FULL snapshots (the pre-v12
    posture) vs the default ASYNC INCREMENTAL plane — at two keyed-
    state sizes. checkpoint_save_ms is the BARRIER-side cost in both
    modes (capture + write sync, capture + budget-wait async), so its
    p99 is the directly-comparable stall; bytes_delta is what actually
    hit disk, so async/sync delta ratio is the incremental win. Both
    legs must produce byte-identical sink output (the exactly-once
    contract is not allowed to depend on the plane posture). Like
    phase O this documents a cost surface, not a rate."""
    import tempfile

    from tpustream import StreamExecutionEnvironment
    from tpustream.config import ObsConfig, StreamConfig
    from tpustream.jobs.chapter2_max import build
    from tpustream.runtime.sources import ReplaySource

    def pick(series, name, field=None):
        for s in series:
            if s["name"] == name:
                return s["value"][field] if field else s["value"]
        return None

    def run(lines, keys, async_, incremental):
        with tempfile.TemporaryDirectory() as ckdir:
            env = StreamExecutionEnvironment(StreamConfig(
                batch_size=max(8, len(lines) // 8),
                key_capacity=keys * 2,
                checkpoint_dir=ckdir,
                checkpoint_interval_batches=1,
                checkpoint_async=async_,
                checkpoint_incremental=incremental,
                obs=ObsConfig(enabled=True),
            ))
            handle = build(
                env, env.add_source(ReplaySource(lines))
            ).collect()
            env.execute("checkpoint-probe")
            series = env.metrics.obs_snapshot()["metrics"]["series"]
        return handle.items, series

    def leg_stats(series):
        return {
            # p99 catches the worst barrier (the post-compile first cut
            # in both legs — comparable); p50 is the steady-state stall
            "barrier_stall_ms_p99": pick(series, "checkpoint_save_ms", "p99"),
            "barrier_stall_ms_p50": pick(series, "checkpoint_save_ms", "p50"),
            "capture_ms_p50": pick(series, "checkpoint_capture_ms", "p50"),
            "write_wall_ms_p50": pick(
                series, "checkpoint_write_wall_ms", "p50"
            ),
            "snapshots": pick(series, "checkpoint_bytes", "count"),
            "bytes_state": pick(series, "checkpoint_bytes", "sum"),
            "bytes_written": pick(series, "checkpoint_bytes_delta", "sum"),
            "chunks_reused": pick(series, "checkpoint_chunks_reused_total"),
        }

    out = {}
    for label, keys in sizes:
        # every key appears twice so the second half of the run churns
        # values but mints no new keys — the incremental plane's case
        lines = [
            f"15634520{j % 60:02d} 10.{(j % keys) >> 8}.{(j % keys) & 255}.9 "
            f"cpu{j % 3} {(j * 13) % 100}.5"
            for j in range(keys * 2)
        ]
        sync_items, sync_series = run(
            lines, keys, async_=False, incremental=False
        )
        async_items, async_series = run(
            lines, keys, async_=True, incremental=True
        )
        sync_leg, async_leg = leg_stats(sync_series), leg_stats(async_series)
        stall_ratio = (
            round(sync_leg["barrier_stall_ms_p99"]
                  / async_leg["barrier_stall_ms_p99"], 2)
            if sync_leg["barrier_stall_ms_p99"]
            and async_leg["barrier_stall_ms_p99"] else None
        )
        delta_ratio = (
            round(async_leg["bytes_written"] / sync_leg["bytes_written"], 3)
            if async_leg["bytes_written"] and sync_leg["bytes_written"]
            else None
        )
        out[label] = {
            "keys": keys,
            "sync_full": sync_leg,
            "async_incremental": async_leg,
            # barrier p99 sync/async: >1 means the async plane moved
            # write cost off the hot path at this state size
            "barrier_stall_ratio": stall_ratio,
            # bytes-to-disk async/sync: <1 is the incremental win
            "delta_bytes_ratio": delta_ratio,
            "outputs_identical": (
                _sink_digest(sync_items) == _sink_digest(async_items)
            ),
        }
    worst = max(
        (s["async_incremental"]["barrier_stall_ms_p99"] or 0.0)
        for s in out.values()
    )
    out["barrier_stall_ms"] = round(worst, 3)
    out["outputs_identical"] = all(
        s["outputs_identical"] for s in out.values()
        if isinstance(s, dict)
    )
    return out


def dynamic_rules_probe():
    """Phase U: dynamic-rules propagation probe (docs/dynamic_rules.md).
    Runs the chapter-5 dynamic-threshold job with a mid-stream broadcast
    update and reports what a runtime rule change costs: the ingest ->
    first-batch-under-new-rule latency series the executor mints
    (``rule_update_propagation_ms``), the update/version counters, and
    the zero-recompile proof (``operator_recompile_cause`` must show no
    ``config_change`` builds). Documents a surface, not a rate."""
    from tpustream import StreamExecutionEnvironment
    from tpustream.config import ObsConfig, StreamConfig
    from tpustream.jobs.chapter5_dynamic_rules import (
        build, control_lines, make_rules, oracle,
    )
    from tpustream.runtime.sources import ReplaySource

    lines = [
        f"15634520{j % 100:02d} 10.8.22.{j % 5} cpu{j % 3} "
        f"{60 + (j * 13) % 40}.5"
        for j in range(2048)
    ]
    updates = [(512, 95.0), (1536, 75.0)]
    env = StreamExecutionEnvironment(
        StreamConfig(batch_size=256, obs=ObsConfig(enabled=True))
    )
    rules = make_rules()
    handle = build(
        env,
        env.add_source(ReplaySource(lines)),
        env.add_source(ReplaySource(control_lines(updates))),
        rules,
    ).collect()
    env.execute("dynamic-rules-probe")
    series = env.metrics.obs_snapshot()["metrics"]["series"]

    def pick(name, field=None):
        for s in series:
            if s["name"].endswith(name):
                return s["value"][field] if field else s["value"]
        return None

    config_change_builds = sum(
        s["value"]
        for s in series
        if s["name"] == "operator_recompile_cause"
        and s["labels"].get("cause") == "config_change"
    )
    want = [tuple(t) for t in oracle(lines, updates)]
    got = [tuple(t) for t in handle.items]
    return dict(
        updates_applied=pick("rule_updates_total"),
        rule_version=pick("rule_version"),
        propagation_ms_p50=pick("rule_update_propagation_ms", "p50"),
        propagation_ms_p99=pick("rule_update_propagation_ms", "p99"),
        config_change_recompiles=config_change_builds,
        output_matches_oracle=got == want,
    )


def multitenancy_probe(tenant_counts=(1, 16, 64, 256),
                       records_per_tenant=64, batch_size=256):
    """Phase T: multi-tenant multiplexing sweep (docs/multitenancy.md).
    Runs the chapter-6 tenant fleet at 1/16/64/256 tenants — each fleet
    is ONE compiled program with [T] rule vectors — and reports
    throughput and per-batch cost vs tenant count, plus one hot
    per-tenant rule write mid-stream per fleet: its propagation latency
    series and the zero-recompile proof (``operator_recompile_cause``
    must show no ``config_change`` builds at any fleet size)."""
    import time as _time

    from tpustream.config import ObsConfig, StreamConfig
    from tpustream.jobs import chapter6_tenant_fleet as c6

    sweep = []
    series = []
    for T in tenant_counts:
        thresholds = {f"t{i:03d}": 80.0 + (i % 20) for i in range(T)}
        srv = c6.make_fleet(
            thresholds,
            tenant_capacity=T,
            config=StreamConfig(
                batch_size=batch_size, obs=ObsConfig(enabled=True)
            ),
        )
        lines = {
            t: c6.tenant_lines(t, records_per_tenant) for t in thresholds
        }
        half = records_per_tenant // 2
        for t in thresholds:
            srv.ingest(t, lines[t][:half])
        # a hot per-tenant rule-row write mid-stream: fleet shape intact
        srv.update_tenant_rules("t000", {"threshold": 83.0})
        for t in thresholds:
            srv.ingest(t, lines[t][half:])
        t0 = _time.perf_counter()
        srv.run(f"fleet-{T}")
        wall_s = _time.perf_counter() - t0
        total = T * records_per_tenant
        n_batches = max(1, -(-total // batch_size))
        series = srv.env.metrics.obs_snapshot()["metrics"]["series"]
        config_change_builds = sum(
            s["value"]
            for s in series
            if s["name"] == "operator_recompile_cause"
            and s["labels"].get("cause") == "config_change"
        )
        probe = "t000"
        want = c6.expected(
            probe, lines[probe], thresholds[probe],
            [(0, thresholds[probe]), (half, 83.0)],
        )
        sweep.append(dict(
            tenants=T,
            events_per_s=round(total / wall_s) if wall_s else None,
            ms_per_batch=round(wall_s * 1000.0 / n_batches, 3),
            config_change_recompiles=config_change_builds,
            updated_tenant_matches_oracle=(
                [tuple(x) for x in srv.output(probe)]
                == [tuple(x) for x in want]
            ),
        ))

    def pick(name, field=None):  # from the largest fleet's registry
        for s in series:
            if s["name"].endswith(name):
                return s["value"][field] if field else s["value"]
        return None

    return dict(
        sweep=sweep,
        propagation_ms_p50=pick("rule_update_propagation_ms", "p50"),
        all_outputs_match=all(
            e["updated_tenant_matches_oracle"] for e in sweep
        ),
        zero_config_change_recompiles=all(
            e["config_change_recompiles"] == 0 for e in sweep
        ),
    )


def tenant_slo_probe(tenants=64, records_per_tenant=16, flood_factor=20,
                     batch_size=256):
    """Phase T, SLO leg: noisy-neighbor attribution
    (docs/multitenancy.md). One fleet with a per-tenant SLO on every
    tenant; ``t000`` floods ``flood_factor``x its quota. Reports the
    flooder's attributed error rate, its compiled SLO verdict and
    budget burn, how many OTHER tenants stayed OK on their own series
    (the isolation proof), and what one ``/tenants.json`` fleet view
    costs to assemble."""
    import time as _time

    from tpustream.config import ObsConfig, StreamConfig
    from tpustream.jobs import chapter6_tenant_fleet as c6
    from tpustream.obs.slo import TenantSLO

    thresholds = {f"t{i:03d}": 80.0 + (i % 20) for i in range(tenants)}
    srv = c6.make_fleet(
        thresholds,
        quotas={"t000": records_per_tenant},
        tenant_capacity=tenants,
        config=StreamConfig(
            batch_size=batch_size, obs=ObsConfig(enabled=True)
        ),
    )
    slo = TenantSLO(p99_ms=1e6, max_error_rate=0.01, budget_window_s=60.0)
    for t in thresholds:
        srv.set_tenant_slo(t, slo)
    offered = 0
    for t in thresholds:
        n = records_per_tenant * (flood_factor if t == "t000" else 1)
        srv.ingest(t, c6.tenant_lines(t, n))
        offered += n
    t0 = _time.perf_counter()
    srv.run(f"fleet-slo-{tenants}")
    wall_s = _time.perf_counter() - t0
    t0 = _time.perf_counter()
    view = srv.tenants_snapshot()
    scrape_ms = (_time.perf_counter() - t0) * 1000.0
    flood = view["tenants"]["t000"]
    verdict = flood["health"]["slo_err[t000]"]
    others_ok = sum(
        1 for t, e in view["tenants"].items()
        if t != "t000"
        and all(r["level"] == "ok" for r in e.get("health", {}).values())
    )
    latency_series = sum(
        1 for e in view["tenants"].values() if "e2e_p99_ms" in e
    )
    return dict(
        tenants=tenants,
        flood_factor=flood_factor,
        events_per_s=round(offered / wall_s) if wall_s else None,
        flooder_error_rate=round(flood["error_rate"], 4),
        flooder_level=verdict["level"],
        flooder_budget_burn=verdict["budget_burn"],
        others_ok=others_ok,
        tenants_with_latency_series=latency_series,
        tenants_json_scrape_ms=round(scrape_ms, 3),
    )


def sustainable_rate(run_paced, r0, label, rtt_ms):
    """Rate -> p99 curve with stage attribution (VERDICT r4 next #1),
    walking a descending rate ladder from the flood throughput ``r0``.

    Each rung paces the source at the target rate with ARRIVAL-SIZED
    batches (fill target = max(100 ms, 2.2x the measured host-device
    round trip, which keeps the batch cadence above that round trip).
    A rung is SUSTAINABLE
    when (a) the source never slips its schedule materially (achieved
    >= 93% of target — explicit backpressure instead of an unbounded
    queue) and (b) the full-path p95 (fill wait + measured batch-close
    -> dispatch) is fully ATTRIBUTED by its stages: p95_full <= fill +
    host parse + fetch wait (p90 of step entries) + one round trip +
    100 ms margin. An unattributed excess means queueing — the rung is
    over capacity no matter how it was achieved. The gate is p95;
    p99_full is still reported per rung.

    Returns (best_rung, curve): best = the highest sustainable rung
    (or the last tried, marked unsustainable); curve = every rung's
    attributed record, for the BENCH record."""
    best = None
    curve = []
    fill_target = max(100.0, 2.2 * rtt_ms)
    for frac in (0.8, 0.55, 0.35, 0.2, 0.1, 0.05):
        target = r0 * frac
        res = run_paced(target, fill_target)
        res["target_rate"] = target
        budget = (
            res["fill_ms"]
            + (res["host_ms_med"] or 0.0)
            + (res["step_ms_p90"] or 0.0)
            + rtt_ms
            + 100.0
        )
        res["attributed_budget_ms"] = budget
        ok = (
            res["rate"] >= 0.93 * target
            and res["p95_full_ms"] is not None
            and res["p95_full_ms"] <= budget
        )
        res["sustainable"] = ok
        curve.append(
            {
                k: res[k]
                for k in (
                    "target_rate", "rate", "rows_per_batch", "fill_ms",
                    "p50_full_ms", "p95_full_ms", "p99_full_ms",
                    "host_ms_med", "step_ms_p90", "attributed_budget_ms",
                    "behind_s", "sustainable",
                )
            }
        )
        log(
            f"  {label} @ {target/1e6:.2f}M target (batch "
            f"{res['rows_per_batch']}, fill {res['fill_ms']:.0f} ms) -> "
            f"achieved {res['rate']/1e6:.2f}M, full-path p50 "
            f"{res['p50_full_ms'] and round(res['p50_full_ms'])} ms, p95 "
            f"{res['p95_full_ms'] and round(res['p95_full_ms'])} ms, p99 "
            f"{res['p99_full_ms'] and round(res['p99_full_ms'])} ms "
            f"(attributed budget {budget:.0f} = fill {res['fill_ms']:.0f} "
            f"+ host {res['host_ms_med'] and round(res['host_ms_med'])} "
            f"+ fetch {res['step_ms_p90'] and round(res['step_ms_p90'])} "
            f"+ rtt {rtt_ms:.0f} + 100), behind {res['behind_s']:.2f}s -> "
            f"{'SUSTAINABLE' if ok else 'unattributed excess / slip'}"
        )
        if ok:
            # descending ladder: the first sustainable rung is the
            # highest sustainable rate
            return res, curve
        best = res  # else keep the lowest rung tried, marked unsustainable
    return best, curve


def host_chain_rate():
    """The FULL host stage short of H2D, measured as one pipelined rate
    (VERDICT r2 next #4): raw bytes -> native ISO parse + key intern ->
    columnar Batch -> int32-delta pack. This is the chain the
    'parse-bound ~10M lines/s/core on PCIe hosts' claim rests on; each
    stage was previously measured alone, never as one chain."""
    from tpustream import StreamExecutionEnvironment, Time, TimeCharacteristic
    from tpustream.config import StreamConfig
    from tpustream.jobs.chapter3_bandwidth_eventtime import build
    from tpustream.runtime.executor import HostStage, Runner
    from tpustream.runtime.metrics import Metrics
    from tpustream.runtime.plan import build_plan_chain

    BL, NKEY = 1 << 16, 1 << 20
    tpl, tcols = _render_flagship_lines(BL, NKEY)
    cfg = StreamConfig(
        batch_size=BL, key_capacity=NKEY, alert_capacity=1 << 16,
    )
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    sink = []
    build(
        env, env.add_source(None), size=Time.seconds(5), slide=Time.seconds(1)
    ).add_sink(lambda r: sink.append(r))
    plan = build_plan_chain(env, env._sinks)[0]
    host = HostStage(plan, cfg)
    # the Runner only supplies _pack here; shrink its key state so the
    # device-side allocation is negligible (interning still covers the
    # full 1M-key space through the shared plan tables)
    import dataclasses

    runner = Runner(
        plan, dataclasses.replace(cfg, key_capacity=1024), Metrics()
    )

    src = _GenBytesSource(tpl, tcols, 40, 5, BL, 1_566_957_600_000)
    n_lines = 0
    for sb in src.batches(BL, 0.0):
        if sb.final:
            break
        batch, _ = host.process_raw(sb.raw, sb.n_raw, sb.proc_ts)
        assert batch is not None, "native raw lane unavailable"
        runner._pack(
            [np.asarray(c.data) for c in batch.columns],
            np.asarray(batch.valid),
            np.asarray(batch.ts),
        )
        n_lines += sb.n_raw
    rate = src.steady_rate()
    return rate, n_lines


def ingest_lane_sweep(lane_counts=(1, 2, 4), nbuf=30, warm=5,
                      bl=1 << 16, nkey=1 << 20):
    """Phase I2: sharded host ingestion (runtime/ingest.py). The same
    raw-bytes -> parse+intern -> Batch chain as phase I, but driven
    through the IngestPlane (StreamConfig.ingest_lanes) at each lane
    count. A sha256 over every merged column and the ts vector proves
    the merge contract: each lane count must reproduce the lanes=1
    stream byte-for-byte, so any speedup is free of semantic drift."""
    import hashlib

    from tpustream import StreamExecutionEnvironment, Time, TimeCharacteristic
    from tpustream.config import StreamConfig
    from tpustream.jobs.chapter3_bandwidth_eventtime import build
    from tpustream.runtime.executor import HostStage
    from tpustream.runtime.ingest import build_ingest_plane
    from tpustream.runtime.metrics import Metrics, Stopwatch
    from tpustream.runtime.plan import build_plan_chain

    tpl, tcols = _render_flagship_lines(bl, nkey)
    sweep = {
        "lines_per_run": nbuf * bl,
        "timed_lines": (nbuf - warm) * bl,
        "results": [],
    }
    base_digest = None
    for lanes in lane_counts:
        cfg = StreamConfig(
            batch_size=bl, key_capacity=nkey, alert_capacity=1 << 16,
            ingest_lanes=lanes,
        )
        env = StreamExecutionEnvironment(cfg)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        build(
            env, env.add_source(None), size=Time.seconds(5),
            slide=Time.seconds(1),
        ).add_sink(lambda r: None)
        plan = build_plan_chain(env, env._sinks)[0]
        host = HostStage(plan, cfg)

        def prepare(sb):
            # mirrors the executor's _prepare: final/empty frames are
            # host-routed by the plane and must pass through unparsed
            with Stopwatch() as hw:
                if sb.final or sb.n_records == 0:
                    return sb, None, None, hw
                batch, wm = host.process_raw(sb.raw, sb.n_raw, sb.proc_ts)
                assert batch is not None, "native raw lane unavailable"
                return sb, batch, wm, hw

        src = _GenBytesSource(tpl, tcols, nbuf, warm, bl, 1_566_957_600_000)
        plane = None
        if lanes > 1:
            plane = build_ingest_plane(
                host, cfg.resolve()[0], plan, Metrics().job_obs,
                single_process=True,
            )
            assert plane is not None, "ingest plane refused to build"
            frames = plane.frames(src.batches(bl, 0.0), prepare)
        else:
            frames = map(prepare, src.batches(bl, 0.0))
        h = hashlib.sha256()
        n_lines = 0
        try:
            for _sb, batch, _wm, _hw in frames:
                if batch is None:
                    continue
                for col in batch.columns:
                    h.update(np.ascontiguousarray(col.data).tobytes())
                h.update(np.ascontiguousarray(batch.ts).tobytes())
                n_lines += batch.n
        finally:
            if plane is not None:
                plane.close()
        digest = h.hexdigest()
        if base_digest is None:
            base_digest = digest
        rate = src.steady_rate()
        sweep["results"].append(
            {
                "lanes": lanes,
                "lines_per_s": round(rate),
                "sha256": digest,
                "byte_identical_to_1_lane": digest == base_digest,
                "n_lines": n_lines,
            }
        )
        log(
            f"  ingest lanes={lanes}: {rate/1e6:.2f}M lines/s, "
            f"digest {'==' if digest == base_digest else '!='} 1-lane"
        )
        assert digest == base_digest, (
            f"lane merge broke byte parity at lanes={lanes}"
        )
    return sweep


def device_ch3_tumbling(stream_hash):
    """Config 3 device pipeline: processing-time 1-min tumbling sum
    (chapter3 BandwidthMonitor) driven by an on-device generator with
    the virtual processing clock advancing at 10M records/s."""
    import importlib.util

    import jax
    import jax.numpy as jnp

    from tpustream import StreamExecutionEnvironment, TimeCharacteristic
    from tpustream.config import StreamConfig
    from tpustream.jobs.chapter3_bandwidth import build
    from tpustream.runtime.plan import build_plan
    from tpustream.runtime.sources import ReplaySource
    from tpustream.runtime.step import build_program

    B, K = 1 << 17, 1 << 20
    TUM_SIM = 1_000_000  # slower intrinsic rate -> each step carries
    #                      131 ms of stream, so ~2-3 one-minute window
    #                      fires land inside the measured interval
    cfg = StreamConfig(
        batch_size=B, key_capacity=K, alert_capacity=1 << 16,
        acc_dtype="int32", max_fires_per_step=4,
    )
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.ProcessingTime)
    text = env.add_source(ReplaySource([]))
    build(env, text).collect()
    plan = build_plan(env, env._sinks)
    program = build_program(plan, cfg)

    rec_per_ms = TUM_SIM // 1000
    t0 = BASE_MS

    def gen(i):
        g, h = stream_hash(i, B)
        keys = (h % K).astype(jnp.int32)
        flow = jnp.where((keys & 127) == 0, 1, 1_000_000)
        ts = t0 + g // rec_per_ms
        return (keys, flow), jnp.ones(B, bool), ts

    def chunk(state, tot, i):
        def body(carry, _):
            state, tot, i = carry
            cols, valid, ts = gen(i)
            wm = t0 + (i + 1) * (B // rec_per_ms) - 1
            state, em = program._step(state, cols, valid, ts, wm)
            return (state, tot + em["main"]["mask"].sum(), i + 1), None

        (state, tot, i), _ = jax.lax.scan(
            body, (state, tot, i), None, length=CHUNK
        )
        return state, tot, i

    cj = jax.jit(chunk, donate_argnums=0)
    state = program.init_state()
    tot = jnp.asarray(0, jnp.int64)
    i = jnp.asarray(0, jnp.int64)
    state, tot, i = cj(state, tot, i)
    _ = np.asarray(tot)
    for _ in range(3):  # warm past the first 1-min window fire
        state, tot, i = cj(state, tot, i)
    _ = np.asarray(tot)
    t1 = time.perf_counter()
    CH = 6
    for _ in range(CH):
        state, tot, i = cj(state, tot, i)
    _ = np.asarray(tot)
    dt = time.perf_counter() - t1
    return CH * CHUNK * B / dt, int(np.asarray(tot))


def measure_rtt(n=6):
    """Bare host-device round trip: fetch a FRESHLY computed device
    scalar each time (re-fetching one buffer may be served from a host
    cache and read ~0). Median over ``n`` fetches — the irreducible
    per-device_get cost."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda i: i + 1)
    x = f(jnp.asarray(0, jnp.int32))
    _ = np.asarray(jax.device_get(x))
    ts = []
    for _ in range(n):
        x = f(x)
        t0 = time.perf_counter()
        _ = np.asarray(jax.device_get(x))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e3)


def _scan_bench(program, gen_fn, wm_fn, B_, warm_chunks, timed_chunks,
                chunk_len=None):
    """Shared chained-scan device-pipeline methodology: CHUNK steps per
    jitted dispatch, alert tally carried on device, one fetch per chunk.
    ``gen_fn(i) -> (cols, valid, ts)``, ``wm_fn(i) -> wm_lower``.
    Returns (events_per_s, alerts)."""
    import jax
    import jax.numpy as jnp

    CL = chunk_len or CHUNK

    def chunk(state, tot, i):
        def body(carry, _):
            state, tot, i = carry
            cols, valid, ts = gen_fn(i)
            state, em = program._step(state, cols, valid, ts, wm_fn(i))
            return (state, tot + em["main"]["mask"].sum(), i + 1), None

        (state, tot, i), _ = jax.lax.scan(
            body, (state, tot, i), None, length=CL
        )
        return state, tot, i

    cj = jax.jit(chunk, donate_argnums=0)
    state = program.init_state()
    tot = jnp.asarray(0, jnp.int64)
    i = jnp.asarray(0, jnp.int64)
    for _ in range(warm_chunks):
        state, tot, i = cj(state, tot, i)
    _ = np.asarray(tot)
    t0 = time.perf_counter()
    for _ in range(timed_chunks):
        state, tot, i = cj(state, tot, i)
    _ = np.asarray(tot)
    dt = time.perf_counter() - t0
    return timed_chunks * CL * B_ / dt, int(np.asarray(tot))


def _program_for(job_builder, cfg, time_char):
    """Build one device program from a job builder over an empty replay
    source (the standard plan -> program path, no executor)."""
    from tpustream import StreamExecutionEnvironment
    from tpustream.runtime.plan import build_plan
    from tpustream.runtime.sources import ReplaySource
    from tpustream.runtime.step import build_program

    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(time_char)
    text = env.add_source(ReplaySource([]))
    job_builder(env, text).collect()
    plan = build_plan(env, env._sinks)
    return build_program(plan, cfg)


def device_session(stream_hash):
    """Phase K (VERDICT r4 weak #6): session windows (gap-based merged
    cells) device pipeline. Stream design: an 8192-key ACTIVE block
    rotates every 2 stream-seconds over a 128K key space, so each
    retired block's sessions close one gap after rotation — fires run
    continuously at steady state instead of never (uniform keys at this
    rate would extend every session forever)."""
    import jax.numpy as jnp

    from tpustream import (
        BoundedOutOfOrdernessTimestampExtractor,
        Time,
        TimeCharacteristic,
        Tuple2,
    )
    from tpustream.api.windows import EventTimeSessionWindows
    from tpustream.config import StreamConfig
    from tpustream.javacompat import Long

    B_s, K_s, ACTIVE = 1 << 17, 1 << 17, 1 << 13
    GAP_MS, DELAY_MS = 1_000, 1_000
    rec_per_ms = SIM_RATE // 1000

    class Ts(BoundedOutOfOrdernessTimestampExtractor):
        def __init__(self):
            super().__init__(Time.milliseconds(DELAY_MS))

        def extract_timestamp(self, value):
            return int(value.split(" ")[0])

    def job(env, text):
        return (
            text.assign_timestamps_and_watermarks(Ts())
            .map(lambda l: Tuple2(l.split(" ")[1], Long.parseLong(l.split(" ")[2])))
            .key_by(0)
            .window(EventTimeSessionWindows.with_gap(Time.milliseconds(GAP_MS)))
            .reduce(lambda a, b: Tuple2(a.f0, a.f1 + b.f1))
        )

    cfg = StreamConfig(
        batch_size=B_s, key_capacity=K_s, alert_capacity=1 << 14,
        acc_dtype="int32",
        # ~8192 sessions close per block rotation; the ring only needs
        # to span session length (<= 2 s) + gap + delay over 1 s panes
        fire_capacity=1 << 14, session_extra_panes=16,
    )
    program = _program_for(job, cfg, TimeCharacteristic.EventTime)

    def gen(i):
        g, h = stream_hash(i, B_s)
        ts = BASE_MS + g // rec_per_ms
        block = g // (2_000 * rec_per_ms)
        keys = ((h % ACTIVE) + block * ACTIVE) % K_s
        return (
            (keys.astype(jnp.int32), jnp.ones(B_s, dtype=jnp.int64)),
            jnp.ones(B_s, bool),
            ts,
        )

    LONG_MIN_ = -(2 ** 62)
    return _scan_bench(
        program, gen, lambda i: jnp.asarray(LONG_MIN_, jnp.int64),
        B_s, warm_chunks=3, timed_chunks=5, chunk_len=50,
    )


def device_count_window(stream_hash, B_c=1 << 17, K_c=1 << 17, N=50,
                        warm=2, timed=4):
    """Phase L (VERDICT r4 weak #6): tumbling count windows — the
    destructive per-key (acc, cnt) fold with window boundaries as extra
    segment starts; fires every N-th element of a key, no time
    machinery at all. Called again at the v5e-8 PER-SHARD shape
    (B/8, K/8) for the sharded compute-side aggregate, like rolling's
    phase D2 (the sort is O(B log B), so eight 16K-row per-shard sorts
    beat one 131K-row sort; the keyBy all_to_all is unmeasurable on
    one chip and moves ~12 B/row over ICI)."""
    import jax.numpy as jnp

    from tpustream import Tuple2
    from tpustream.config import StreamConfig
    from tpustream.javacompat import Long

    def job(env, text):
        return (
            text.map(lambda l: Tuple2(l.split(" ")[1], Long.parseLong(l.split(" ")[2])))
            .key_by(0)
            .count_window(N)
            .reduce(lambda a, b: Tuple2(a.f0, a.f1 + b.f1))
        )

    from tpustream import TimeCharacteristic

    cfg = StreamConfig(
        batch_size=B_c, key_capacity=K_c, alert_capacity=1 << 16,
        acc_dtype="int32",
    )
    program = _program_for(job, cfg, TimeCharacteristic.ProcessingTime)

    def gen(i):
        _, h = stream_hash(i, B_c)
        keys = (h % K_c).astype(jnp.int32)
        return (
            (keys, jnp.ones(B_c, dtype=jnp.int64)),
            jnp.ones(B_c, bool),
            jnp.zeros(B_c, dtype=jnp.int64),
        )

    return _scan_bench(
        program, gen, lambda i: jnp.asarray(0, jnp.int64),
        B_c, warm_chunks=warm, timed_chunks=timed, chunk_len=50,
    )


def device_chain(stream_hash):
    """Phase M (VERDICT r4 weak #6): a two-stage chain — tumbling 5 s
    window sums re-keyed into a 15 s rollup — BOTH stages inside one
    jitted scan, stage 2 consuming stage 1's compacted emission buffer
    directly (the device-side cost of the chain; the host glue's
    cross-shard ordering is correctness machinery measured by the
    executor-path phases). Rate is stage-1 input events/s."""
    import jax
    import jax.numpy as jnp

    from tpustream import (
        BoundedOutOfOrdernessTimestampExtractor,
        StreamExecutionEnvironment,
        Time,
        TimeCharacteristic,
        Tuple2,
    )
    from tpustream.config import StreamConfig
    from tpustream.javacompat import Long
    from tpustream.runtime.plan import build_plan_chain
    from tpustream.runtime.sources import ReplaySource
    from tpustream.runtime.step import build_program

    B_1, K_1 = 1 << 17, 1 << 16
    CAP = 1 << 17  # stage-1 emission buffer = stage-2 batch
    rec_per_ms = SIM_RATE // 1000

    class Ts(BoundedOutOfOrdernessTimestampExtractor):
        def __init__(self):
            super().__init__(Time.seconds(2))

        def extract_timestamp(self, value):
            return int(value.split(" ")[0])

    add = lambda a, b: Tuple2(a.f0, a.f1 + b.f1)
    cfg1 = StreamConfig(
        batch_size=B_1, key_capacity=K_1, alert_capacity=CAP,
        acc_dtype="int32",
    )
    env = StreamExecutionEnvironment(cfg1)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    text = env.add_source(ReplaySource([]))
    (
        text.assign_timestamps_and_watermarks(Ts())
        .map(lambda l: Tuple2(l.split(" ")[1], Long.parseLong(l.split(" ")[2])))
        .key_by(0)
        .time_window(Time.seconds(5))
        .reduce(add)
        .key_by(0)
        .time_window(Time.seconds(15))
        .reduce(add)
        .collect()
    )
    plans = build_plan_chain(env, env._sinks)
    p1 = build_program(plans[0], cfg1)
    plans[1].record_kinds.extend(p1.out_kinds)
    plans[1].tables.extend(p1.out_tables)
    cfg2 = StreamConfig(
        batch_size=CAP, key_capacity=K_1, alert_capacity=CAP,
        acc_dtype="int32",
    )
    p2 = build_program(plans[1], cfg2)

    LONG_MIN_ = -(2 ** 62)

    def gen(i):
        g, h = stream_hash(i, B_1)
        ts = BASE_MS + g // rec_per_ms
        keys = (h % K_1).astype(jnp.int32)
        return (keys, jnp.ones(B_1, dtype=jnp.int64)), jnp.ones(B_1, bool), ts

    def chunk(carry, tot, i):
        def body(inner, _):
            (s1, s2), tot, i = inner
            cols, valid, ts = gen(i)
            s1, em1 = p1._step(s1, cols, valid, ts, LONG_MIN_)
            m = em1["main"]
            s2, em2 = p2._step(
                s2, m["cols"], m["mask"], m["window_end"] - 1, LONG_MIN_
            )
            tot = tot + em2["main"]["mask"].sum()
            return ((s1, s2), tot, i + 1), None

        (carry, tot, i), _ = jax.lax.scan(
            body, (carry, tot, i), None, length=50
        )
        return carry, tot, i

    cj = jax.jit(chunk, donate_argnums=0)
    carry = (p1.init_state(), p2.init_state())
    tot = jnp.asarray(0, jnp.int64)
    i = jnp.asarray(0, jnp.int64)
    # warm through the first stage-2 fire: a 15 s rollup window closes
    # when stage 1 emits a 20 s window end (stream t ~= 22 s = 1700
    # steps of 13.1 ms); timing starts past it so the timed segment
    # carries steady two-stage fire traffic
    for _ in range(36):
        carry, tot, i = cj(carry, tot, i)
    _ = np.asarray(tot)
    t0 = time.perf_counter()
    TIMED = 24
    tot0 = int(np.asarray(tot))
    for _ in range(TIMED):
        carry, tot, i = cj(carry, tot, i)
    _ = np.asarray(tot)
    dt = time.perf_counter() - t0
    return TIMED * 50 * B_1 / dt, int(np.asarray(tot)) - tot0


def device_cep(stream_hash, B_p=1 << 17, key_counts=(1 << 14, 1 << 17),
               lengths=(2, 3, 5), warm=2, timed=3, chunk_len=50):
    """Phase P: CEP pattern throughput — the vectorized on-device NFA
    (runtime/cep_program.py) swept over keys x pattern length. Stream:
    uniform keys, ~1/4 of events breach the threshold, so with
    ``times(L).consecutive()`` partials form and die continuously
    (~4^-L of events complete a match); ``within(1 s)`` keeps the
    watermark timeout sweep active every step. Per-event device work is
    the [B, L] advance + one register-plane scatter, so the sweep shows
    how rate moves with L (register planes) and K (state height)."""
    import jax.numpy as jnp

    from tpustream import (
        BoundedOutOfOrdernessTimestampExtractor,
        CEP,
        Pattern,
        Time,
        TimeCharacteristic,
        Tuple2,
    )
    from tpustream.config import StreamConfig
    from tpustream.javacompat import Long

    rec_per_ms = SIM_RATE // 1000
    WITHIN_MS = 1_000

    class Ts(BoundedOutOfOrdernessTimestampExtractor):
        def __init__(self):
            super().__init__(Time.seconds(1))

        def extract_timestamp(self, value):
            return int(value.split(" ")[0])

    def one(K_p, L_p):
        def job(env, text):
            keyed = (
                text.assign_timestamps_and_watermarks(Ts())
                .map(
                    lambda l: Tuple2(
                        l.split(" ")[1], Long.parseLong(l.split(" ")[2])
                    )
                )
                .key_by(0)
            )
            pat = (
                Pattern.begin("b").where(lambda r: r.f1 > 500)
                .times(L_p).consecutive()
                .within(Time.milliseconds(WITHIN_MS))
            )
            return CEP.pattern(keyed, pat).select(
                lambda m: Tuple2(m["b"][0].f0, m["b"][-1].f1)
            )

        cfg = StreamConfig(
            batch_size=B_p, key_capacity=K_p, alert_capacity=1 << 16,
        )
        program = _program_for(job, cfg, TimeCharacteristic.EventTime)

        def gen(i):
            g, h = stream_hash(i, B_p)
            ts = BASE_MS + g // rec_per_ms
            keys = (h % K_p).astype(jnp.int32)
            vals = jnp.where((h >> 7) % 4 == 0, 1000, 10).astype(jnp.int64)
            return (keys, vals), jnp.ones(B_p, bool), ts

        LONG_MIN_ = -(2 ** 62)
        return _scan_bench(
            program, gen, lambda i: jnp.asarray(LONG_MIN_, jnp.int64),
            B_p, warm_chunks=warm, timed_chunks=timed, chunk_len=chunk_len,
        )

    sweep = []
    for K_p in key_counts:
        for L_p in lengths:
            rate, matches = one(K_p, L_p)
            sweep.append(
                dict(
                    keys=K_p, pattern_len=L_p,
                    events_per_s=round(rate), matches=matches,
                )
            )
            log(
                f"phase P: CEP L={L_p}, {K_p} keys: {rate/1e6:.1f}M "
                f"events/s/chip, {matches} matches"
            )
    return dict(batch=B_p, within_ms=WITHIN_MS, sweep=sweep)


def _sink_digest(rows):
    """Order-insensitive content hash of a sink's emissions. Pipeline
    depths change WHEN windows fire relative to the feed loop, never
    WHAT fires, so the sorted-repr digest is the right equality."""
    h = hashlib.sha256()
    for r in sorted(repr(x) for x in rows):
        h.update(r.encode())
        h.update(b"\n")
    return h.hexdigest()


def decompose_full_path(n_batches=10, bl=1 << 16, nkey=1 << 20,
                        pipelined=True):
    """Stage-attributed account of the full execute_job path (VERDICT r3
    next #4): run the flagship shape batch by batch SYNCHRONOUSLY and
    time each stage — host parse+intern, delta-pack, H2D+device step
    submit, and the per-batch count fetch — plus the bare host-device
    round trip. Under pipelining (async_depth) stages overlap, so the
    achieved full-path rate is set by the BINDING stage, not the sum;
    this phase names that stage with measured numbers. A second pass
    runs the SAME
    shape through the async executor (staged H2D uploads, device-side
    compaction, deep dispatch queue) so the sync-vs-pipelined ms/batch
    ratio is the measured overlap win. ``bl``/``nkey``/``n_batches``
    are parameters so a tier-1 tiny-mode smoke can exercise the exact
    phase logic without flagship-sized buffers."""
    import jax

    from tpustream import StreamExecutionEnvironment, Time, TimeCharacteristic
    from tpustream.config import StreamConfig
    from tpustream.jobs.chapter3_bandwidth_eventtime import build
    from tpustream.runtime.executor import HostStage, Runner
    from tpustream.runtime.metrics import Metrics
    from tpustream.runtime.plan import build_plan_chain

    def make_runner(cfg, job_obs=None):
        env = StreamExecutionEnvironment(cfg)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        sink = []
        build(
            env, env.add_source(None), size=Time.seconds(5),
            slide=Time.seconds(1),
        ).add_sink(lambda r: sink.append(r))
        plan = build_plan_chain(env, env._sinks)[0]
        if job_obs is None:
            metrics = Metrics()
        else:
            metrics = Metrics(
                registry=job_obs.registry, job_name=job_obs.job_name
            )
            metrics.job_obs = job_obs
        return HostStage(plan, cfg), Runner(plan, cfg, metrics), sink, metrics

    def parse_batch(host, sb):
        """Native raw-bytes lane, falling back to the line path where
        the native parser isn't built (the tier-1 CPU smoke env) — the
        stage decomposition then times the Python parse instead."""
        batch, _ = host.process_raw(sb.raw, sb.n_raw, sb.proc_ts)
        if batch is None:
            lines = bytes(sb.raw).decode().splitlines()[: sb.n_raw]
            batch, _ = host.process(lines, sb.proc_ts)
        return batch

    tpl, tcols = _render_flagship_lines(bl, nkey)
    cfg = StreamConfig(
        batch_size=bl, key_capacity=nkey, alert_capacity=1 << 16,
        async_depth=1, max_batch_delay_ms=0.0,
    )
    host, runner, _, _ = make_runner(cfg)

    src = _GenBytesSource(tpl, tcols, n_batches + 3, 0, bl, 1_566_957_600_000)
    t_parse, t_pack, t_feed, t_rtt = [], [], [], []
    wm_lower = -(2 ** 62)
    raw_bytes = wire_bytes = 0
    b = 0
    for sb in src.batches(bl, 0.0):
        if sb.final:
            break
        t0 = time.perf_counter()
        batch = parse_batch(host, sb)
        t1 = time.perf_counter()
        # pack timed on its own (feed() re-packs internally; the pack is
        # pure numpy and cheap to run twice)
        packed, _, valid_p, ts_p, _ = runner._pack(
            [np.asarray(c.data) for c in batch.columns],
            np.asarray(batch.valid),
            np.asarray(batch.ts),
        )
        # bytes/row before and after the packed wire format (satellite:
        # the wire-ceiling math needs the POST-pack number; the delta is
        # what the narrow format saves)
        raw_bytes = (
            sum(int(np.asarray(c.data).nbytes) for c in batch.columns)
            + int(np.asarray(batch.valid).nbytes)
            + int(np.asarray(batch.ts).nbytes)
        )
        wire_bytes = (
            sum(int(np.asarray(a).nbytes) for a in packed)
            + int(np.asarray(valid_p).nbytes)
            + int(np.asarray(ts_p).nbytes)
        )
        t2 = time.perf_counter()
        runner.feed(batch, wm_lower)
        runner.drain_inflight()
        t3 = time.perf_counter()
        # bare round trip: fetch one already-computed device scalar
        _ = np.asarray(jax.device_get(runner.state["wm"]))
        t4 = time.perf_counter()
        if b >= 3:  # skip compile/warmup batches
            t_parse.append(t1 - t0)
            t_pack.append(t2 - t1)
            t_feed.append(t3 - t2)
            t_rtt.append(t4 - t3)
        b += 1
    med = lambda xs: float(np.median(xs) * 1e3)
    parse_ms, pack_ms, feed_ms, rtt_ms = (
        med(t_parse), med(t_pack), med(t_feed), med(t_rtt)
    )
    # the feed covers pack + H2D + device step + count-fetch RPC +
    # emission fetch; subtracting the separately-measured pack and one
    # RTT (the count fetch) leaves transfer + device compute
    stages = {
        "parse_intern_ms": parse_ms,
        "pack_ms": pack_ms,
        "h2d_step_fetch_ms": feed_ms - pack_ms,
        "count_fetch_rtt_ms": rtt_ms,
        "batch_total_sync_ms": parse_ms + feed_ms,
    }
    sync_rate = bl / ((parse_ms + feed_ms) / 1e3)
    binding = max(
        ("parse_intern_ms", parse_ms),
        ("h2d_step_fetch_ms", feed_ms - pack_ms),
        key=lambda kv: kv[1],
    )

    # pipelined pass: default config (async_depth, h2d_depth staging,
    # compaction) over the same batches; ms/batch here is the overlapped
    # steady-state cost the flood actually pays
    pipelined_ms = pipelined_rate = None
    baseline_sha = None
    if pipelined:
        cfg2 = StreamConfig(
            batch_size=bl, key_capacity=nkey, alert_capacity=1 << 16,
            max_batch_delay_ms=0.0,
        )
        host2, runner2, sink2, _ = make_runner(cfg2)
        src2 = _GenBytesSource(
            tpl, tcols, n_batches + 3, 0, bl, 1_566_957_600_000
        )
        b2 = 0
        t_start = None
        for sb in src2.batches(bl, 0.0):
            if sb.final:
                break
            batch = parse_batch(host2, sb)
            if b2 == 3:  # warm batches compiled + drained; clock starts
                runner2.drain_inflight()
                t_start = time.perf_counter()
            # real watermark progress (each buffer = one stream second)
            # so windows fire and the pass pays the emission path the
            # flood pays — and leaves sink bytes to hold against the
            # controller-on pass below
            runner2.feed(batch, int(np.asarray(batch.ts).max()))
            b2 += 1
        runner2.drain_inflight()
        if t_start is not None and b2 > 3:
            pipelined_ms = (time.perf_counter() - t_start) / (b2 - 3) * 1e3
            pipelined_rate = bl / (pipelined_ms / 1e3)
        baseline_sha = _sink_digest(sink2)

    # controller-on pass: same shape again with the obs layer live and
    # the AdaptiveController driven at batch barriers (the bench stands
    # in for the Snapshotter tick). The contract under test: knobs move
    # only inside bounds, every move is a flight event + controller_*
    # series, and the sink bytes match the controller-off pass exactly —
    # depths overlap work, they never change results.
    controller_report = None
    if pipelined:
        from tpustream.config import ObsConfig
        from tpustream.obs.runtime import JobObs
        from tpustream.runtime.controller import AdaptiveController

        obs_cfg = ObsConfig(
            enabled=True, adaptive=True, adaptive_cooldown_ticks=0,
        )
        cfg3 = StreamConfig(
            batch_size=bl, key_capacity=nkey, alert_capacity=1 << 16,
            max_batch_delay_ms=0.0, obs=obs_cfg,
        )
        job_obs3 = JobObs(obs_cfg, job_name="decompose")
        host3, runner3, sink3, metrics3 = make_runner(cfg3, job_obs3)
        controller = AdaptiveController(cfg3, job_obs3)
        src3 = _GenBytesSource(
            tpl, tcols, n_batches + 3, 0, bl, 1_566_957_600_000
        )
        b3 = 0
        t_start3 = None
        for sb in src3.batches(bl, 0.0):
            if sb.final:
                break
            batch = parse_batch(host3, sb)
            if b3 == 3:
                runner3.drain_inflight()
                t_start3 = time.perf_counter()
            runner3.feed(batch, int(np.asarray(batch.ts).max()))
            if b3 >= 3:  # tick once per steady-state batch
                knobs = controller.on_tick()
                if knobs:
                    runner3.drain_inflight()
                    for r in runner3.chain():
                        r.apply_knobs(knobs)
            b3 += 1
        runner3.drain_inflight()
        ctl_ms = ctl_rate = None
        if t_start3 is not None and b3 > 3:
            ctl_ms = (time.perf_counter() - t_start3) / (b3 - 3) * 1e3
            ctl_rate = bl / (ctl_ms / 1e3)
        summary3 = controller.summary()
        prof = {}
        if job_obs3.profiler is not None:
            prof = job_obs3.profiler.profile()
        lat3 = sorted(metrics3.emit_latencies_s)
        p99_ms3 = (
            float(np.percentile(lat3, 99) * 1e3) if lat3 else None
        )
        output_sha = _sink_digest(sink3)
        controller_report = dict(
            converged=controller.converged(),
            bounds=summary3["bounds"],
            decisions=summary3["decisions"],
            reverts=summary3["reverts"],
            p99_ms=p99_ms3,
            ms_per_batch=ctl_ms,
            rows_per_s=ctl_rate,
            binding_stage=prof.get("binding_stage"),
            binding_share=prof.get("binding_share"),
            output_sha=output_sha,
            baseline_sha=baseline_sha,
        )
        knob_txt = ", ".join(
            f"{k}={v}" for k, v in sorted(controller.converged().items())
        )
        log(
            f"phase F detail: controller-on pass converged to {knob_txt} "
            f"after {summary3['decisions']} decisions "
            f"({summary3['reverts']} reverts), emit p99 "
            f"{0.0 if p99_ms3 is None else p99_ms3:.1f} ms, output "
            f"{'MATCHES' if output_sha == baseline_sha else 'DIVERGES FROM'}"
            f" the controller-off pass"
        )
        job_obs3.close(dump=False)

    return dict(
        rows_per_batch=bl,
        wire_bytes_per_row=wire_bytes / bl,
        bytes_per_row_raw=raw_bytes / bl,
        bytes_per_row_packed=wire_bytes / bl,
        stages_ms=stages,
        sync_rows_per_s=sync_rate,
        binding_stage=binding[0],
        binding_ms=binding[1],
        pipelined_ms_per_batch=pipelined_ms,
        pipelined_rows_per_s=pipelined_rate,
        controller=controller_report,
    )


def measure_h2d():
    """The H2D bandwidth actually available to batches.

    Sequential small ``device_put`` calls each pay a full round trip
    before the next dispatches, so they measure the round trip, not
    the wire. Two fixes: (1) each pass ships ONE batched
    ``jax.device_put`` of all chunks so the runtime streams them
    back-to-back, and (2) the bare fetch RTT of the closing scalar —
    measured separately against an already-resident array — is
    subtracted from the elapsed wall so the reported rate is transfer
    time, not round-trip residency."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    chunk = 4 << 20
    n_chunks = 8
    rng = np.random.default_rng(0)
    arrs = [
        rng.integers(0, 127, chunk, dtype=np.int8) for _ in range(n_chunks)
    ]
    consume = jax.jit(
        lambda xs: sum(jnp.sum(x, dtype=jnp.int32) for x in xs)
    )
    _ = np.asarray(consume(jax.device_put(arrs, dev)))  # compile + warm
    # bare round trip: fetch of an already-device-resident scalar
    resident = consume(jax.device_put(arrs, dev))
    _ = np.asarray(resident)
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        _ = np.asarray(resident)
        rtts.append(time.perf_counter() - t0)
    rtt = float(np.median(rtts))
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        tot = consume(jax.device_put(arrs, dev))
        _ = np.asarray(tot)
        el = max(1e-9, time.perf_counter() - t0 - rtt)
        rates.append(n_chunks * chunk / el / 1e6)
    rates.sort()
    log(
        f"phase H detail: batched H2D passes "
        f"{', '.join(f'{r:.0f}' for r in rates)} MB/s after subtracting "
        f"the {rtt*1e3:.1f} ms closing-fetch RTT (median reported)"
    )
    return rates[1]


# ---------------------------------------------------------------------------
# bench --compare: per-phase deltas behind a comparability verdict
# ---------------------------------------------------------------------------
# Pure stdlib on purpose: comparing two BENCH files must not need jax,
# a device, or even this repo's runtime — only the env-fingerprint
# comparability logic is imported (lazily) from tpustream.obs.resources.

#: |delta| beyond this on a directional phase counts as a regression /
#: improvement; smaller moves are reported as noise-level
REGRESSION_PCT = 10.0
#: a lane sweep is inverse-scaling when the max-lane rate lands below
#: this fraction of the single-lane rate
INVERSE_SCALING_RATIO = 0.9

_HIGHER_BETTER = ("_per_s", "_per_sec", "throughput")
_LOWER_BETTER = ("_ms", "latency", "_s_p99", "overhead_pct")


def _phase_direction(name: str):
    """+1 higher-is-better, -1 lower-is-better, 0 no direction."""
    n = name.lower()
    if any(n.endswith(s) or s in n for s in _HIGHER_BETTER):
        return 1
    if any(n.endswith(s) for s in _LOWER_BETTER) or "latency" in n:
        return -1
    return 0


def _flatten_phases(detail, prefix="", out=None):
    """Numeric leaves of a record's detail dict, dotted-key flattened.
    Lists are skipped (the lane sweep is handled structurally)."""
    if out is None:
        out = {}
    if not isinstance(detail, dict):
        return out
    for k, v in detail.items():
        key = f"{prefix}{k}"
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[key] = float(v)
        elif isinstance(v, dict):
            _flatten_phases(v, prefix=key + ".", out=out)
    return out


def _lane_sweep_results(detail):
    """[(lanes, lines_per_s), ...] from an ingest_lane_sweep section
    anywhere in the detail tree, or None."""
    if not isinstance(detail, dict):
        return None
    sweep = detail.get("ingest_lane_sweep")
    if isinstance(sweep, dict) and isinstance(sweep.get("results"), list):
        out = []
        for r in sweep["results"]:
            if isinstance(r, dict) and "lanes" in r and "lines_per_s" in r:
                out.append((int(r["lanes"]), float(r["lines_per_s"])))
        if len(out) >= 2:
            return sorted(out)
    for v in detail.values():
        if isinstance(v, dict):
            found = _lane_sweep_results(v)
            if found is not None:
                return found
    return None


def load_bench_record(path):
    """One BENCH artifact -> {path, env, phases, lane_sweep, error}.

    Accepts both shapes in the repo's history: a raw record (the one
    JSON line a bench run prints: metric/value/unit/detail, schema>=2
    adds env) and the round wrapper ({n, cmd, rc, tail, parsed}) whose
    record is either ``parsed`` or the last ``BENCH {json}`` line of
    the stderr tail. A wrapper with neither (r05: the record line was
    truncated) loads with ``error`` set and no env — which downstream
    makes the round incomparable, never silently comparable."""
    with open(path, "r") as f:
        doc = json.load(f)
    rec = doc
    if isinstance(doc, dict) and "tail" in doc and "cmd" in doc:
        rec = doc.get("parsed")
        if not isinstance(rec, dict):
            rec = None
            for line in str(doc.get("tail", "")).splitlines():
                if line.startswith("BENCH "):
                    try:
                        rec = json.loads(line[len("BENCH "):])
                    except ValueError:
                        pass
        if rec is None:
            return {
                "path": path, "env": None, "phases": {},
                "lane_sweep": None, "schema": 0,
                "error": "no parseable BENCH record in round wrapper",
            }
    detail = {}
    for key in ("detail", "round_detail"):
        if isinstance(rec.get(key), dict):
            detail = rec[key]
            break
    phases = _flatten_phases(detail)
    if isinstance(rec.get("value"), (int, float)) and not isinstance(
        rec.get("value"), bool
    ):
        phases["headline"] = float(rec["value"])
    env = rec.get("env") if isinstance(rec.get("env"), dict) else None
    return {
        "path": path,
        "env": env,
        "phases": phases,
        "lane_sweep": _lane_sweep_results(detail),
        "schema": int(rec.get("bench_schema", 1) or 1),
        "error": None,
    }


def check_lane_scaling(sweep):
    """Inverse-scaling verdict over [(lanes, rate), ...]: more lanes
    should never cost throughput. None when the sweep is absent."""
    if not sweep:
        return None
    base_lanes, base_rate = sweep[0]
    top_lanes, top_rate = sweep[-1]
    inverse = (
        base_rate > 0
        and top_lanes > base_lanes
        and top_rate < INVERSE_SCALING_RATIO * base_rate
    )
    return {
        "inverse": bool(inverse),
        "base": {"lanes": base_lanes, "rate": base_rate},
        "top": {"lanes": top_lanes, "rate": top_rate},
        "top_over_base": round(top_rate / base_rate, 3) if base_rate else None,
    }


def _env_comparability(old, new):
    """(comparable, reasons) across two loaded records."""
    reasons = []
    for rec, which in ((old, "OLD"), (new, "NEW")):
        if rec["error"]:
            reasons.append(f"{which} {rec['path']}: {rec['error']}")
        elif rec["env"] is None:
            reasons.append(
                f"{which} {rec['path']}: no environment fingerprint "
                f"(pre-schema-2 record)"
            )
    if reasons:
        return False, reasons
    EnvFingerprint = _resources_module().EnvFingerprint
    diff = EnvFingerprint.from_dict(old["env"]).comparability(
        EnvFingerprint.from_dict(new["env"])
    )
    return (not diff), diff


def _resources_module():
    """tpustream/obs/resources.py loaded standalone (stdlib-only file),
    so the compare path never pays the package's jax import."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "tpustream", "obs", "resources.py",
    )
    spec = importlib.util.spec_from_file_location("tsm_obs_resources", path)
    mod = importlib.util.module_from_spec(spec)
    # dataclass field-type resolution looks the module up by name
    sys.modules.setdefault("tsm_obs_resources", mod)
    spec.loader.exec_module(mod)
    return sys.modules["tsm_obs_resources"]


def compare_records(old, new):
    """The full comparison document for two loaded records."""
    comparable, reasons = _env_comparability(old, new)
    result = {
        "old": old["path"],
        "new": new["path"],
        "comparable": comparable,
        "verdict": "comparable" if comparable else "incomparable environments",
        "reasons": reasons,
        "deltas": [],
        "regressions": [],
        "improvements": [],
        "lane_scaling_old": check_lane_scaling(old["lane_sweep"]),
        "lane_scaling_new": check_lane_scaling(new["lane_sweep"]),
    }
    if not comparable:
        return result
    for name in sorted(set(old["phases"]) & set(new["phases"])):
        a, b = old["phases"][name], new["phases"][name]
        if a == 0:
            continue
        pct = (b - a) / abs(a) * 100.0
        direction = _phase_direction(name)
        entry = {
            "phase": name, "old": a, "new": b, "delta_pct": round(pct, 2),
        }
        result["deltas"].append(entry)
        if direction and abs(pct) >= REGRESSION_PCT:
            regressed = pct < 0 if direction > 0 else pct > 0
            (result["regressions"] if regressed
             else result["improvements"]).append(entry)
    return result


def run_compare(paths, gate=False):
    """CLI driver. Exit codes: 0 comparable (and gate clean), 1 file /
    usage error, 2 gate failure (--gate with a regression or inverse
    lane scaling), 3 incomparable environments."""
    try:
        records = [load_bench_record(p) for p in paths]
    except (OSError, ValueError) as e:
        log(f"compare: cannot load record: {e}")
        return 1

    if len(records) == 1:
        rec = records[0]
        scaling = check_lane_scaling(rec["lane_sweep"])
        doc = {
            "file": rec["path"],
            "bench_schema": rec["schema"],
            "env": rec["env"],
            "error": rec["error"],
            "phases": rec["phases"],
            "lane_scaling": scaling,
        }
        print(json.dumps(doc, indent=2, sort_keys=True))
        if scaling and scaling["inverse"]:
            log(
                f"compare: INVERSE LANE SCALING in {rec['path']}: "
                f"{scaling['top']['lanes']} lanes at "
                f"{scaling['top_over_base']}x the 1-lane rate"
            )
            if gate:
                return 2
        return 1 if rec["error"] else 0

    old, new = records
    result = compare_records(old, new)
    print(json.dumps(result, indent=2, sort_keys=True))
    if not result["comparable"]:
        log(
            "compare: VERDICT incomparable environments — refusing any "
            "speedup/regression claim:"
        )
        for r in result["reasons"]:
            log(f"  - {r}")
        return 3
    inverse = any(
        s and s["inverse"]
        for s in (result["lane_scaling_old"], result["lane_scaling_new"])
    )
    if inverse:
        log("compare: inverse lane scaling detected (see lane_scaling_*)")
    for e in result["regressions"]:
        log(
            f"compare: regression {e['phase']}: {e['old']:g} -> "
            f"{e['new']:g} ({e['delta_pct']:+.1f}%)"
        )
    log(
        f"compare: VERDICT comparable — {len(result['deltas'])} shared "
        f"phase(s), {len(result['regressions'])} regression(s), "
        f"{len(result['improvements'])} improvement(s)"
    )
    if gate and (result["regressions"] or inverse):
        return 2
    return 0


def main(argv=None):
    """No args: run the full bench. ``--compare OLD.json [NEW.json]``:
    offline record comparison (no jax import); ``--gate`` makes
    regressions and inverse lane scaling exit nonzero for CI."""
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--compare", nargs="+", metavar="BENCH.json",
        help="compare two BENCH records (or summarize one) instead of "
        "running the bench; refuses cross-environment claims",
    )
    ap.add_argument(
        "--gate", action="store_true",
        help="with --compare: exit 2 on a regression or inverse lane "
        "scaling (exit 3 stays: incomparable environments)",
    )
    args = ap.parse_args(argv)
    if args.compare:
        if len(args.compare) > 2:
            ap.error("--compare takes one or two record files")
        sys.exit(run_compare(args.compare, gate=args.gate))
    run_bench()
    if _FAILED_PHASES:
        log(f"{len(_FAILED_PHASES)} phase(s) failed: {', '.join(_FAILED_PHASES)}")
        sys.exit(1)


def run_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graft_entry", __file__.replace("bench.py", "__graft_entry__.py")
    )
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    log(f"device: {dev}, batch={B}, keys={K}, sim_rate={SIM_RATE/1e6:.0f}M ev/s")

    t_build = time.perf_counter()
    program, cfg = ge._build_flagship(1, B, K)
    wm0 = jnp.asarray(-(2**62), jnp.int64)
    rec_per_ms = SIM_RATE // 1000

    def stream_hash(i, n):
        """Deterministic per-record mix shared by every phase's stream
        generator (Knuth multiplicative hash + xor-shift)."""
        g = i * n + jnp.arange(n, dtype=jnp.int64)
        h = g * 2654435761
        return g, h ^ (h >> 29)

    def gen(i):
        """Batch i of the synthetic stream: uniform keys, ~1% alerting
        (low-flow) channels, up to 10 s of bounded out-of-orderness."""
        g, h = stream_hash(i, B)
        keys = (h % K).astype(jnp.int32)
        alerting = (keys & 127) == 0
        flow = jnp.where(alerting, 1, 1_000_000)
        jitter = (h >> 33) % 10_000
        ts = BASE_MS + g // rec_per_ms - jitter
        return (ts // 1000, keys, flow), jnp.ones(B, bool), ts

    def chunk(state, tot, i):
        def body(carry, _):
            state, tot, i = carry
            cols, valid, ts = gen(i)
            state, em = program._step(state, cols, valid, ts, wm0)
            tot = (
                tot[0] + em["main"]["mask"].sum(),
                tot[1] + em["late"]["mask"].sum(),
            )
            return (state, tot, i + 1), None

        (state, tot, i), _ = jax.lax.scan(
            body, (state, tot, i), None, length=CHUNK
        )
        return state, tot, i

    chunk_j = jax.jit(chunk, donate_argnums=0)

    state = program.init_state()
    tot = (jnp.asarray(0, jnp.int64), jnp.asarray(0, jnp.int64))
    i = jnp.asarray(0, jnp.int64)
    state, tot, i = chunk_j(state, tot, i)
    _ = np.asarray(tot[0])
    log(f"build + compile + first chunk: {time.perf_counter()-t_build:.1f}s")

    # warm through the watermark delay AND one full window size, so the
    # timed region sees steady state: during the ramp every partially
    # filled window alerts (the Mbps filter sees low sums), which is a
    # stream artifact, not steady behavior. Each step carries
    # B/SIM_RATE = 13.1 ms of stream.
    stream_ms_per_step = B * 1000 // SIM_RATE
    warm_steps = (
        program.delay_ms + program.ring.size_ms + 2 * program.ring.slide_ms
    ) // stream_ms_per_step
    warm_chunks = int(warm_steps) // CHUNK + 1
    t0 = time.perf_counter()
    for _ in range(warm_chunks):
        state, tot, i = chunk_j(state, tot, i)
    _ = np.asarray(tot[0])
    log(
        f"warmup: {warm_chunks*CHUNK} steps in {time.perf_counter()-t0:.1f}s, "
        f"wm at {int(np.asarray(state['wm'])) - BASE_MS} ms of stream, "
        f"{int(np.asarray(tot[0]))} alerts so far"
    )

    # ---- Phase A: sustained device throughput ---------------------------
    # Two estimators over the same 2000 steps: (a) the pipelined total
    # (10 async chunk dispatches, one fetch) and (b) the MEDIAN of
    # per-chunk sync walls (each chunk fetched, so one host stall
    # inflates only its own chunk, not the whole interval). The
    # reported rate is the max of the two.
    CH = 10  # 2000 steps, ~26 s of stream: ~5 slide fires at real cadence
    a0, l0 = int(np.asarray(tot[0])), int(np.asarray(tot[1]))
    ovf0 = int(np.asarray(state["alert_overflow"]))
    ev0 = int(np.asarray(state["evicted_unfired"]))
    t0 = time.perf_counter()
    chunk_walls = []
    pending = None
    for _ in range(CH):
        t_c = time.perf_counter()
        state, tot, i = chunk_j(state, tot, i)
        if pending is not None:
            # fetch the PREVIOUS chunk's tally while this one runs:
            # the wait ends when that chunk's device work does, so each
            # wall ~= one chunk's device time with the RTT hidden under
            # the next dispatch (tot is not donated — safe to read)
            _ = np.asarray(pending[0])
        pending = tot
        chunk_walls.append(time.perf_counter() - t_c)
    _ = np.asarray(tot[0])
    dt = time.perf_counter() - t0
    total_alerts = int(np.asarray(tot[0])) - a0
    total_late = int(np.asarray(tot[1])) - l0
    events = CH * CHUNK * B
    med_wall = float(np.median(chunk_walls[1:]))  # [0] has no fetch
    rate = max(events / dt, CHUNK * B / med_wall)
    stream_s = events / SIM_RATE
    alert_ovf = int(np.asarray(state["alert_overflow"])) - ovf0
    evicted = int(np.asarray(state["evicted_unfired"])) - ev0
    log(
        f"phase A: {CH*CHUNK} steps ({events/1e6:.0f}M events, "
        f"{stream_s:.1f}s of stream) in {dt:.3f}s total, median chunk "
        f"{med_wall:.3f}s -> {rate/1e6:.2f}M events/s/chip "
        f"({med_wall/CHUNK*1e3:.3f} ms/step median); "
        f"{total_alerts} alerts, {total_late} late-dropped, "
        f"{alert_ovf} overflowed, {evicted} evicted-unfired"
    )

    # ---- Phase B: ingest -> alert latency -------------------------------
    # deployment p99 = batch residency + FIRING-step device time (alerts
    # leave pre-compacted over PCIe). The firing-step time is measured
    # robustly by chaining 30 forced-fire steps on device (wm_lower
    # advanced one slide per step, the processing-time-tick hint) — one
    # dispatch, one fetch.
    slide = program.ring.slide_ms

    def fire_chunk(state, i, wm_start):
        def body(carry, j):
            state, i = carry
            cols, valid, ts = gen(i)
            state, em = program._step(
                state, cols, valid, ts, wm_start + (j + 1) * slide
            )
            return (state, i + 1), em["main"]["mask"].sum()

        (state, i), fired = jax.lax.scan(
            body, (state, i), jnp.arange(30, dtype=jnp.int64)
        )
        return state, i, fired

    fire_j = jax.jit(fire_chunk, donate_argnums=0)
    wm_now = int(np.asarray(state["wm"]))
    state, i, fired_v = fire_j(state, i, jnp.asarray(wm_now, jnp.int64))
    _ = np.asarray(fired_v)  # compile
    wm_now = int(np.asarray(state["wm"]))
    t1 = time.perf_counter()
    state, i, fired_v = fire_j(state, i, jnp.asarray(wm_now, jnp.int64))
    fired_v = np.asarray(fired_v)
    fire_step_ms = (time.perf_counter() - t1) / 30 * 1e3
    fired = int(fired_v[-1])

    residency_ms = B / SIM_RATE * 1e3
    p99_dev = residency_ms + fire_step_ms
    log(
        f"phase B: firing step emits {fired} alerts in {fire_step_ms:.1f} ms "
        f"device time; ingest->alert p99 {p99_dev:.1f} ms device-side "
        f"(incl. {residency_ms:.1f} ms batch residency)"
    )

    # ---- Phase D: rolling-aggregate config (BASELINE.json config 2) -----
    # chapter2-style keyed running max, measured with the same
    # chained-scan methodology; failures here never sink the headline
    def rolling_device_bench(B_r, K_r, scan_len, warm, timed):
        """Chained-scan rolling-max benchmark at (batch, keys); returns
        events/s. Warmup runs past the coupon-collector horizon so the
        steady-state no-new-keys cond branch is what gets timed."""
        from tpustream.ops import rolling as R

        KINDS = ["str", "str", "f64"]
        compact = [False, False, True]
        combine = R.make_combiner("max", 2)

        def rgen(i):
            _, h = stream_hash(i, B_r)
            return (h % K_r).astype(jnp.int32), (
                (h % K_r).astype(jnp.int32),
                (h % 8).astype(jnp.int32),
                (h % 10000).astype(jnp.float64) / 100.0,
            )

        def rmulti(rstate, tot, i):
            def body(carry, _):
                rstate, tot, i = carry
                keys, rcols = rgen(i)
                rstate, emis, sv, sk, inv = R.rolling_step(
                    rstate, keys, rcols, jnp.ones(B_r, bool), combine,
                    KINDS, compact,
                    rolling_kind="max", rolling_pos=2, key_col=0,
                    key_emit=lambda s: s.astype(jnp.int32),
                    sentinel_leaf=1,
                )
                return (rstate, tot + emis[2].sum(), i + 1), None

            (rstate, tot, i), _ = jax.lax.scan(
                body, (rstate, tot, i), None, length=scan_len
            )
            return rstate, tot, i

        rmulti_j = jax.jit(rmulti, donate_argnums=0)
        rstate = R.init_rolling_state(K_r, KINDS, compact, sentinel_leaf=1)
        rtot = jnp.asarray(0.0, jnp.float64)
        ri = jnp.asarray(0, jnp.int64)
        for _ in range(warm):
            rstate, rtot, ri = rmulti_j(rstate, rtot, ri)
        _ = np.asarray(rtot)
        t0 = time.perf_counter()
        for _ in range(timed):
            rstate, rtot, ri = rmulti_j(rstate, rtot, ri)
        _ = np.asarray(rtot)
        return timed * scan_len * B_r / (time.perf_counter() - t0)

    rolling_rate = None
    try:
        rolling_rate = rolling_device_bench(1 << 17, K, 100, 2, 3)
        log(
            f"phase D: rolling max (1M keys): {rolling_rate/1e6:.1f}M "
            f"events/s/chip"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("D", e)

    # ---- Phase D2: rolling at the PER-SHARD shape (VERDICT r3 weak #7) --
    # Sharded rolling pays one per-shard sort (B/S rows into K/S keys)
    # plus the keyBy all_to_all. This environment has ONE real chip, so
    # the exchange cannot be measured; what CAN be measured is the
    # per-shard compute at the v5e-8 shard shape (B/8, K/8). Because the
    # rolling step is sort-bound and sort is O(n log n), 8 shards
    # sorting 16K rows each in parallel beat one 131K-row sort — the
    # per-shard measurement bounds the 8-chip aggregate from the
    # compute side; the all_to_all rides ICI (~100 GB/s/link) and moves
    # only ~17 B/row, so compute remains the binding stage.
    rolling_shard_rate = None
    try:
        rolling_shard_rate = rolling_device_bench(
            (1 << 17) // 8, K // 8, 200, 3, 3
        )
        log(
            f"phase D2: rolling at the v5e-8 PER-SHARD shape "
            f"(B/8={(1 << 17) // 8}, K/8={K // 8}): "
            f"{rolling_shard_rate/1e6:.1f}M events/s/shard; 8-shard "
            f"compute-side aggregate ~{rolling_shard_rate*8/1e6:.0f}M ev/s "
            f"(exchange unmeasurable on 1 chip; ~17 B/row over ICI)"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("D2", e)

    # ---- Phase E: ch3 tumbling, processing time (config 3) --------------
    tumbling_rate = None
    try:
        tumbling_rate, tum_alerts = device_ch3_tumbling(stream_hash)
        log(
            f"phase E: ch3 tumbling (processing time, 1M keys): "
            f"{tumbling_rate/1e6:.1f}M events/s/chip, {tum_alerts} alerts"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("E", e)

    # ---- round trip: the irreducible per-device_get cost ---------------
    rtt_ms = None
    try:
        rtt_ms = measure_rtt()
        log(f"round trip (one device scalar fetch): {rtt_ms:.0f} ms")
    except Exception as e:  # pragma: no cover
        phase_failed("round trip", e)
    rtt = rtt_ms or 100.0

    # ---- Phase F: ch1 threshold FULL PATH (config 1) --------------------
    # F1 floods (throughput ceiling) with the count-fetch RTT amortized
    # over fetch_group=8 steps (VERDICT r4 next #2); F2 walks the paced
    # rate ladder with ARRIVAL-SIZED batches and attributes each rung's
    # p99 into fill + parse + fetch + RTT (VERDICT r4 next #1)
    ch1_rate = None
    ch1_sus = None
    ch1_curve = None
    try:
        f1 = full_path_ch1(fetch_group=16, async_depth=16)
        ch1_rate = f1["rate"]
        log(
            f"phase F1: ch1 full path FLOOD (execute_job, raw bytes, "
            f"fetch_group=16): {ch1_rate/1e6:.2f}M events/s, "
            f"{f1['alerts']} alerts"
        )
        log(f"phase F1 summary: {f1['summary']}")

        def run_ch1(r, fill):
            BL = 1 << 16
            nbuf = min(120, max(3, int(r * 28 / BL) + 1))
            return full_path_ch1(
                rate=r, nbuf=nbuf, warm=max(1, nbuf // 6), fill_ms=fill
            )

        ch1_sus, ch1_curve = sustainable_rate(
            run_ch1, ch1_rate, label="phase F2 ch1", rtt_ms=rtt
        )
    except Exception as e:  # pragma: no cover
        phase_failed("F", e)

    # ---- Phase G: flagship FULL PATH (configs 4/5 end to end) -----------
    full_rate = None
    full_p99 = None
    flag_sus = None
    flag_curve = None
    g1_perstep_rate = None
    try:
        g1 = full_path_flagship(fetch_group=16, async_depth=16)
        full_rate, full_p99 = g1["rate"], g1["p99_ms"]
        p99_txt = f"{full_p99:.0f} ms" if full_p99 is not None else "n/a"
        log(
            f"phase G1: flagship full path FLOOD (execute_job, raw bytes, "
            f"event time, fetch_group=16): {full_rate/1e6:.2f}M events/s, "
            f"p99 ingest->alert {p99_txt} (queueing artifact under flood — "
            f"see G2 for the steady-state figure), {g1['alerts']} alerts"
        )
        log(f"phase G1 summary: {g1['summary']}")
        # the per-step-fetch comparison run names the lever's size —
        # identical knobs except fetch_group, so the ratio isolates it
        g1p = full_path_flagship(
            fetch_group=1, async_depth=16, nbuf=100, warm=40
        )
        g1_perstep_rate = g1p["rate"]
        log(
            f"phase G1a: same flood with per-step count fetches "
            f"(fetch_group=1): {g1_perstep_rate/1e6:.2f}M events/s "
            f"(grouping buys {full_rate/max(g1_perstep_rate,1):.2f}x here)"
        )

        def run_flag(r, fill):
            BL = 1 << 16
            # warm must cover the event-time ramp: delay 2 s + size 5 s
            # = first fires after ~8 stream-seconds (8 BL-line buffers)
            steady = max(6, int(r * 25 / BL) + 1)
            return full_path_flagship(
                rate=r, nbuf=9 + steady, warm=9, fill_ms=fill, delay_s=2
            )

        flag_sus, flag_curve = sustainable_rate(
            run_flag, full_rate, label="phase G2 flagship", rtt_ms=rtt
        )
    except Exception as e:  # pragma: no cover
        phase_failed("G", e)

    # ---- Phase I: host chain rate (parse->Batch->pack, no H2D) ----------
    chain_rate = None
    try:
        chain_rate, chain_lines = host_chain_rate()
        log(
            f"phase I: host chain (raw bytes -> native parse+intern -> "
            f"Batch -> delta-pack, no H2D): {chain_rate/1e6:.2f}M lines/s"
            f"/core over {chain_lines/1e6:.1f}M lines"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("I", e)

    # ---- Phase I2: sharded ingestion lane sweep (docs/performance.md) ---
    lane_sweep = None
    try:
        log("phase I2: sharded ingestion (IngestPlane), lane sweep:")
        lane_sweep = ingest_lane_sweep()
        peak = max(lane_sweep["results"], key=lambda r: r["lines_per_s"])
        base = lane_sweep["results"][0]
        log(
            f"phase I2: best {peak['lanes']} lane(s) at "
            f"{peak['lines_per_s']/1e6:.2f}M lines/s "
            f"({peak['lines_per_s']/max(base['lines_per_s'],1):.2f}x over "
            f"1 lane), all lane counts byte-identical"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("I2", e)

    # ---- Phase H: measured H2D bandwidth (environment context) ----------
    h2d_mb_s = None
    try:
        h2d_mb_s = measure_h2d()
        log(f"phase H: H2D bandwidth (consumed-on-device): {h2d_mb_s:.0f} MB/s")
    except Exception as e:  # pragma: no cover
        phase_failed("H", e)

    # ---- Phase J: full-path stage decomposition (VERDICT r3 #4) ---------
    decomp = None
    wire_ceiling = None
    g1_over_wire = None
    try:
        decomp = decompose_full_path()
        s = decomp["stages_ms"]
        log(
            f"phase J: full-path decomposition (per {decomp['rows_per_batch']}"
            f"-row batch, {decomp['bytes_per_row_raw']:.1f} raw -> "
            f"{decomp['bytes_per_row_packed']:.1f} packed wire B/row): "
            f"parse+intern {s['parse_intern_ms']:.1f} ms, pack "
            f"{s['pack_ms']:.1f} ms, H2D+step+fetch "
            f"{s['h2d_step_fetch_ms']:.1f} ms (bare RTT "
            f"{s['count_fetch_rtt_ms']:.1f} ms), sync total "
            f"{s['batch_total_sync_ms']:.1f} ms -> "
            f"{decomp['sync_rows_per_s']/1e6:.2f}M rows/s unpipelined; "
            f"binding stage: {decomp['binding_stage']} "
            f"({decomp['binding_ms']:.1f} ms)"
        )
        if decomp.get("pipelined_ms_per_batch"):
            log(
                f"phase J: pipelined pass (staged H2D + compaction + "
                f"async dispatch): {decomp['pipelined_ms_per_batch']:.1f} "
                f"ms/batch -> {decomp['pipelined_rows_per_s']/1e6:.2f}M "
                f"rows/s, {s['batch_total_sync_ms'] / max(1e-9, decomp['pipelined_ms_per_batch']):.1f}x "
                f"over sync"
            )
        if h2d_mb_s:
            wire_ceiling = (
                h2d_mb_s * 1e6 / decomp["wire_bytes_per_row"]
            )
            if full_rate:
                g1_over_wire = full_rate / wire_ceiling
            log(
                f"phase J: day's wire ceiling {wire_ceiling/1e6:.2f}M rows/s "
                f"({h2d_mb_s:.0f} MB/s / {decomp['wire_bytes_per_row']:.1f} "
                f"B/row); G1 flood achieves "
                f"{(g1_over_wire or 0)*100:.0f}% of it — the residual is "
                f"the measured per-batch stage costs above"
            )
    except Exception as e:  # pragma: no cover
        phase_failed("J", e)

    # ---- Phases K/L/M: session, count, chained device pipelines ---------
    # (VERDICT r4 weak #6: the families added since round 2 had zero
    # events/s figures anywhere)
    session_rate = None
    try:
        session_rate, session_fires = device_session(stream_hash)
        log(
            f"phase K: session windows (gap 1 s, 128K keys, rotating "
            f"8K-key active block): {session_rate/1e6:.1f}M events/s/chip, "
            f"{session_fires} session fires"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("K", e)

    count_rate = None
    count_shard_rate = None
    try:
        count_rate, count_fires = device_count_window(stream_hash)
        log(
            f"phase L: tumbling count windows (N=50, 128K keys): "
            f"{count_rate/1e6:.1f}M events/s/chip, {count_fires} fires"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("L", e)
    try:
        count_shard_rate, _ = device_count_window(
            stream_hash, B_c=(1 << 17) // 8, K_c=(1 << 17) // 8,
            warm=3, timed=6,
        )
        log(
            f"phase L2: count windows at the v5e-8 PER-SHARD shape "
            f"(B/8={(1 << 17) // 8}, K/8={(1 << 17) // 8}): "
            f"{count_shard_rate/1e6:.1f}M events/s/shard; 8-shard "
            f"compute-side aggregate ~{count_shard_rate*8/1e6:.0f}M ev/s "
            f"(exchange unmeasurable on 1 chip; ~12 B/row over ICI)"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("L2", e)

    chain_dev_rate = None
    try:
        chain_dev_rate, chain_fires = device_chain(stream_hash)
        log(
            f"phase M: two-stage chain (5 s windows -> 15 s rollup, 64K "
            f"keys, both stages on device): {chain_dev_rate/1e6:.1f}M "
            f"stage-1 events/s/chip, {chain_fires} stage-2 fires"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("M", e)

    # ---- Phase P: CEP pattern throughput (keys x pattern length) --------
    cep_sweep = None
    try:
        cep_sweep = device_cep(stream_hash)
    except Exception as e:  # pragma: no cover
        phase_failed("P", e)

    # ---- Phase C: native parse throughput -------------------------------
    parse_rate = None
    try:
        from tpustream.hostparse import PlanEvaluator, trace_host_map
        from tpustream.records import STR, StringTable
        from tpustream.jobs.chapter3_bandwidth_eventtime import parse

        plan = trace_host_map(parse)
        tables = [StringTable() if k == STR else None for k in plan.kinds]
        evaluator = PlanEvaluator(plan.outputs, tables)
        if evaluator._native is not None:
            lines = [
                f"2019-08-28T10:{(j//60)%60:02d}:{j%60:02d} www.ch{j%1000}.com {100+j%997}"
                for j in range(500_000)
            ]
            data = "\n".join(lines).encode()
            t0 = time.perf_counter()
            evaluator.parse_bytes(data, len(lines))
            parse_rate = len(lines) / (time.perf_counter() - t0)
            log(f"phase C: native parse {parse_rate/1e6:.1f}M lines/s/core")
    except Exception as e:  # pragma: no cover
        phase_failed("C", e)

    # ---- Phase O: observability snapshot --------------------------------
    obs_snap = None
    try:
        obs_snap = obs_snapshot_probe()
        series = obs_snap.get("metrics", {}).get("series", [])
        n_series = len(series)
        n_spans = obs_snap.get("trace", {}).get("total_spans", 0)
        n_markers = sum(
            int(s["value"]) for s in series
            if s["name"] == "latency_markers_emitted"
        )
        e2e_p99 = max(
            (s["value"]["p99"] for s in series
             if s["type"] == "histogram"
             and s["name"].endswith("e2e_latency_ms")),
            default=0.0,
        )
        health_level = obs_snap.get("health", {}).get("level", "-")

        # device-side registries (docs/observability.md): per-operator
        # compile accounting and HBM state footprint, folded out of the
        # snapshot so the JSON tail answers "what did XLA build and what
        # does its state cost" without spelunking the raw series
        def _by_op(name, value=lambda s: s["value"]):
            return {
                s["labels"].get("operator", "-"): value(s)
                for s in series
                if s["name"] == name and "operator" in s["labels"]
            }

        compile_summary = {
            "compiles": _by_op("operator_compile_count"),
            "recompiles": _by_op("operator_recompile_count"),
            "wall_ms_p50": _by_op(
                "operator_compile_wall_ms", lambda s: s["value"]["p50"]
            ),
            "flops": _by_op("operator_compile_flops"),
            "bytes_accessed": _by_op("operator_compile_bytes_accessed"),
        }
        state_memory = {
            "hbm_state_bytes": _by_op("operator_hbm_state_bytes"),
            "component_bytes": {
                f"{s['labels'].get('operator', '-')}"
                f"/{s['labels'].get('component', '-')}": s["value"]
                for s in series
                if s["name"] == "operator_state_component_bytes"
            },
            "key_table_load_factor": _by_op("operator_key_table_load_factor"),
            "key_cardinality": _by_op("operator_key_cardinality"),
            "hot_key_share": _by_op("operator_hot_key_share"),
        }
        n_compiles = sum(compile_summary["compiles"].values())
        hbm_total = sum(state_memory["hbm_state_bytes"].values())
        log(
            f"phase O: obs-enabled probe job captured {n_series} metric "
            f"series, {n_spans} step spans; {n_markers} latency markers "
            f"(e2e p99 {e2e_p99:.2f} ms), health {health_level}; "
            f"{n_compiles} XLA builds, {hbm_total / 1e3:.1f} KB device state"
        )
    except Exception as e:  # pragma: no cover
        compile_summary = state_memory = None
        phase_failed("O", e)

    # ---- Phase O2: record flight-path tracing overhead ------------------
    tracing = None
    try:
        tracing = trace_overhead_probe()
        log(
            f"phase O2: record tracing at 1% sampling -> "
            f"{tracing['overhead_pct']:+.1f}% wall overhead, "
            f"{tracing['record_traces_total']} flight path(s) captured, "
            f"{tracing['timeline_events_total']} timeline events, "
            f"output identical: {tracing['output_identical']}"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("O2", e)

    # ---- Phase O3: conservation-ledger overhead probe -------------------
    ledger_probe = None
    try:
        ledger_probe = ledger_overhead_probe()
        log(
            f"phase O3: conservation ledger -> "
            f"{ledger_probe['overhead_pct']:+.1f}% wall overhead, "
            f"{ledger_probe['edges_evaluated']} edge(s) evaluated, "
            f"all residuals zero: {ledger_probe['all_residuals_zero']}, "
            f"output identical: {ledger_probe['output_identical']}"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("O3", e)

    # ---- Phase R: supervised recovery probe -----------------------------
    recovery = None
    try:
        recovery = recovery_probe()
        log(
            f"phase R: injected fault -> {recovery['restarts']} restart(s), "
            f"{recovery['replay_batches']} batches replayed in "
            f"{recovery['recovery_wall_ms'] and round(recovery['recovery_wall_ms'])} ms "
            f"(checkpoint save p50 "
            f"{recovery['checkpoint_save_ms_p50'] and round(recovery['checkpoint_save_ms_p50'], 1)} ms), "
            f"output intact: {recovery['output_intact']}"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("R", e)

    # ---- Phase C2: checkpoint-plane overhead probe ----------------------
    checkpointing = None
    try:
        checkpointing = checkpoint_overhead_probe()
        for label in ("small", "large"):
            s = checkpointing[label]
            log(
                f"phase C2: {label} state ({s['keys']} keys) -> barrier "
                f"stall p99 sync/async "
                f"{s['barrier_stall_ratio']}x, bytes-to-disk "
                f"async/sync {s['delta_bytes_ratio']}, output identical: "
                f"{s['outputs_identical']}"
            )
    except Exception as e:  # pragma: no cover
        phase_failed("C2", e)

    # ---- Phase U: dynamic-rules propagation probe -----------------------
    dynamic_rules = None
    try:
        dynamic_rules = dynamic_rules_probe()
        p50 = dynamic_rules["propagation_ms_p50"]
        log(
            f"phase U: {dynamic_rules['updates_applied']} broadcast rule "
            f"update(s) propagated in p50 "
            f"{p50 and round(p50, 2)} ms with "
            f"{dynamic_rules['config_change_recompiles']} config_change "
            f"recompile(s); output matches oracle: "
            f"{dynamic_rules['output_matches_oracle']}"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("U", e)

    # ---- Phase T: multi-tenant multiplexing sweep -----------------------
    multitenancy = None
    try:
        multitenancy = multitenancy_probe()
        top = multitenancy["sweep"][-1]
        log(
            f"phase T: {top['tenants']} tenants through one compiled "
            f"program at {top['events_per_s']} events/s "
            f"({top['ms_per_batch']} ms/batch); zero config_change "
            f"recompiles at every fleet size: "
            f"{multitenancy['zero_config_change_recompiles']}; outputs "
            f"match oracle: {multitenancy['all_outputs_match']}"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("T", e)

    # ---- Phase T, SLO leg: noisy-neighbor attribution -------------------
    tenant_slo = None
    try:
        tenant_slo = tenant_slo_probe()
        log(
            f"phase T slo: {tenant_slo['tenants']}-tenant fleet, one "
            f"tenant flooding {tenant_slo['flood_factor']}x quota: "
            f"flooder error rate {tenant_slo['flooder_error_rate']} -> "
            f"{tenant_slo['flooder_level']} (budget burn "
            f"{tenant_slo['flooder_budget_burn']}), "
            f"{tenant_slo['others_ok']} other tenants OK; "
            f"/tenants.json view in "
            f"{tenant_slo['tenants_json_scrape_ms']} ms"
        )
    except Exception as e:  # pragma: no cover
        phase_failed("T slo", e)

    # schema-2 header: the environment fingerprint makes this round
    # comparable (or provably incomparable) to any other round
    env_fp = None
    try:
        from tpustream.obs.resources import collect_env_fingerprint

        env_fp = collect_env_fingerprint().to_dict()
    except Exception:
        env_fp = None

    print(
        json.dumps(
            {
                "metric": "ch3 sliding-window events/sec/chip (device pipeline)",
                "value": round(rate),
                "unit": "events/s",
                "vs_baseline": round(rate / TARGET, 3),
                "bench_schema": BENCH_SCHEMA,
                "env": env_fp,
                "detail": {
                    # last stderr lines folded in, so the round's
                    # narrative needs no separate bench_stderr.txt
                    "stderr_tail": list(_LOG_TAIL),
                    "p99_alert_latency_ms_device": round(p99_dev, 2),
                    "alerts_emitted": total_alerts,
                    "late_dropped": total_late,
                    "alert_overflow": alert_ovf,
                    "evicted_unfired": evicted,
                    # all five BASELINE.json configs:
                    "config1_ch1_full_path_events_per_s": round(ch1_rate or 0),
                    "config2_rolling_max_events_per_s": round(rolling_rate or 0),
                    # per-shard-shape rolling (sharded compute bound;
                    # the all_to_all is unmeasurable on one chip)
                    "rolling_per_shard_events_per_s": round(
                        rolling_shard_rate or 0
                    ),
                    "config3_ch3_tumbling_events_per_s": round(tumbling_rate or 0),
                    # configs 4+5 are the headline `value` (device pipeline)
                    "flagship_full_path_events_per_s": round(full_rate or 0),
                    # steady-state sustainable figures (rate-controlled,
                    # backpressured — the honest full-path numbers; the
                    # flood p99 is a queueing artifact and is not
                    # reported)
                    "ch1_sustainable_rate_events_per_s": round(
                        (ch1_sus or {}).get("target_rate") or 0
                    ),
                    "ch1_sustainable_p99_full_ms": round(
                        (ch1_sus or {}).get("p99_full_ms") or 0, 1
                    ),
                    "ch1_sustainable": bool((ch1_sus or {}).get("sustainable")),
                    "flagship_sustainable_rate_events_per_s": round(
                        (flag_sus or {}).get("target_rate") or 0
                    ),
                    "flagship_sustainable_p99_full_ms": round(
                        (flag_sus or {}).get("p99_full_ms") or 0, 1
                    ),
                    "flagship_sustainable": bool(
                        (flag_sus or {}).get("sustainable")
                    ),
                    # rate -> p99 curves, stage-attributed per rung
                    # (VERDICT r4 next #1): p99_full = fill wait +
                    # measured batch-close->dispatch; budget = fill +
                    # host + fetch + RTT + 100 ms margin
                    "link_rtt_ms": round(rtt, 1),
                    "rate_p99_curve_ch1": ch1_curve,
                    "rate_p99_curve_flagship": flag_curve,
                    # flood with per-step count fetches, for the
                    # amortization lever's measured size (r4 next #2)
                    "flagship_flood_perstep_fetch_events_per_s": round(
                        g1_perstep_rate or 0
                    ),
                    # family device pipelines (r4 weak #6)
                    "session_window_events_per_s": round(session_rate or 0),
                    "count_window_events_per_s": round(count_rate or 0),
                    "count_window_per_shard_events_per_s": round(
                        count_shard_rate or 0
                    ),
                    "chain_two_stage_events_per_s": round(
                        chain_dev_rate or 0
                    ),
                    # phase P: the CEP NFA device pipeline swept over
                    # keys x pattern length (docs/cep.md)
                    "cep": cep_sweep,
                    # environment context for the full-path numbers
                    "h2d_bandwidth_mb_per_s": round(h2d_mb_s or 0),
                    "native_parse_lines_per_s": round(parse_rate or 0),
                    "host_chain_lines_per_s": round(chain_rate or 0),
                    # phase I2: the host chain through the IngestPlane
                    # per lane count, with the byte-parity digests
                    # (docs/performance.md "Sharded ingestion")
                    "ingest_lane_sweep": lane_sweep,
                    # stage-attributed full-path account (phase J):
                    # measured per-batch stage costs, the day's wire
                    # ceiling, and the flood rate as a fraction of it
                    "full_path_decomposition": decomp,
                    "wire_ceiling_rows_per_s": round(wire_ceiling or 0),
                    "g1_flood_over_wire_ceiling": round(g1_over_wire or 0, 3),
                    # phase O: per-operator counters, watermark-lag
                    # gauge and step-span trace from an obs-enabled
                    # probe job (docs/observability.md; render with
                    # `python -m tpustream.obs.dump`)
                    "obs_snapshot": obs_snap,
                    # phase O2: record flight-path tracing — the 1%-
                    # sampling wall overhead, the byte-identical-output
                    # proof, and a trimmed unified Perfetto timeline
                    # (docs/observability.md "Flight-path tracing")
                    "tracing": tracing,
                    # phase O3: conservation-ledger cost — the on/off
                    # wall overhead, the byte-identical-output proof,
                    # and the per-edge residual + anchor summary
                    # (docs/observability.md "Conservation ledger")
                    "ledger": ledger_probe,
                    # phase R: what supervised execution costs and
                    # delivers after an injected mid-stream crash
                    # (docs/recovery.md)
                    "recovery": recovery,
                    # phase C2: the checkpoint plane's barrier stall and
                    # bytes-to-disk under sync-full vs async-incremental
                    # at two state sizes, with the byte-identical-output
                    # proof (docs/recovery.md "The checkpoint plane")
                    "checkpointing": checkpointing,
                    # phase U: what a runtime broadcast rule update
                    # costs — propagation latency and the zero-recompile
                    # proof (docs/dynamic_rules.md)
                    "dynamic_rules": dynamic_rules,
                    # phase T: N logical jobs multiplexed onto one
                    # compiled step — throughput and per-batch cost vs
                    # tenant count, with the per-fleet zero-recompile
                    # proof (docs/multitenancy.md)
                    "multitenancy": multitenancy,
                    # phase T SLO leg: per-tenant SLO verdicts under one
                    # flooding tenant — noisy-neighbor attribution and
                    # the isolation proof (docs/multitenancy.md)
                    "tenant_slo": tenant_slo,
                    # and its device-side registries, folded: what XLA
                    # built (count/cause/wall/cost) and what the state
                    # pytree costs in HBM per operator/component
                    "compile_summary": compile_summary,
                    "state_memory": state_memory,
                    "failed_phases": list(_FAILED_PHASES),
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
