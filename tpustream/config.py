"""Central runtime configuration.

The reference hardcodes every knob in its jobs (host/port at
chapter1/.../Main.java:17, threshold at :31, window sizes at
chapter2/.../ComputeCpuAvg.java:29, lateness bound at
chapter3/.../BandwidthMonitorWithEventTime.java:30); SURVEY.md §5 asks for
one dataclass centralizing defaults while job scripts stay equally simple.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


@dataclass
class ObsConfig:
    """Observability knobs (tpustream/obs): per-operator metrics,
    step-span tracing, gauges, and periodic snapshots.

    Disabled by default: the executor then wires the null instrument
    twins, so the per-step cost is a handful of no-op attribute calls —
    no registry writes, no span records, no per-record work ever.
    """

    enabled: bool = False             # master switch for the obs layer
    trace: bool = True                # record step spans (when enabled)
    trace_ring_size: int = 4096       # retained spans (oldest overwritten)
    profiler_bridge: bool = False     # wrap spans in
                                      # jax.profiler.TraceAnnotation so a
                                      # jax.profiler.trace() capture shows
                                      # host spans aligned with device work
    step_histogram_samples: int = 8192  # per-operator histogram ring bound
                                        # (count/sum stay exact past it)
    snapshot_interval_s: float = 0.0  # periodic registry+trace snapshots
                                      # from the batch loop; 0 = only the
                                      # on-demand Metrics.obs_snapshot()
    snapshot_path: str = ""           # optional JSONL file the periodic
                                      # snapshotter appends to

    # -- resource plane (obs/resources.py) ----------------------------------
    resources: bool = False
    # True: a ResourceSampler rides the snapshotter's pre-hook and reads
    # /proc at every snapshot tick — host-wide CPU util, process RSS and
    # context switches, per-ingest-lane-worker CPU time and core
    # placement — minting host_cpu_util / lane_cpu_util{lane} /
    # lane_core{lane} / process_rss_bytes / ctx_switches_total{kind},
    # plus a lane_core_contention detector (two busy lanes on one core,
    # or a multi-lane plane pinned at ~1 core of total CPU -> flight
    # breadcrumb + lane_core_contention_total + built-in WARN health
    # rule). Requires snapshot_interval_s > 0 to sample during the run
    # (analyzer rule TSM019 flags the dead-sampler combination). Reads
    # Linux /proc only; elsewhere samples degrade to no-ops.

    # -- end-to-end latency markers (obs/latency.py) ------------------------
    latency_marker_interval_ms: float = 0.0
    # > 0: the source stamps a LatencyMarker into the batch stream every
    # interval; markers ride the data path (pack/dispatch/fetch/emit,
    # through chained stages) and each operator edge / sink records the
    # source->here age into an e2e latency histogram. 0 (default) = no
    # stamper installed, SourceBatch.markers stays None, zero cost.

    # -- sampled record flight-path tracing (obs/tracing_export.py) ---------
    trace_sample_rate: float = 0.0
    # > 0: the source stamper promotes roughly this fraction of records
    # to RecordTrace probes (deterministic stride sampling, at most one
    # per batch) that ride the latency-marker side-channel and collect a
    # span per hop (source, lane_parse, merge, pack, h2d, device_step,
    # fetch, emit, sink). Requires latency_marker_interval_ms > 0 — the
    # markers are the carrier (analyzer rule TSM018 enforces this). The
    # sink-side span trees land in JobObs.traces and the /trace.json
    # Perfetto timeline. 0 (default) = no record lineage, zero cost.
    trace_max_records: int = 256
    # bounded ring of completed record traces retained at the sink
    # (oldest evicted); bounds memory for arbitrarily long jobs

    # -- per-tenant series bounding (docs/multitenancy.md) ------------------
    tenant_series_topk: int = 64
    # fleets label latency/SLO series per tenant; only the top-K active
    # tenants (by admitted records) get their own label value — the rest
    # fold into one "__other__" bucket so a 10k-tenant fleet cannot
    # explode the registry. 0 = every active tenant gets a series.

    # -- self-monitoring health rules (obs/health.py) -----------------------
    health_rules: tuple = ()
    # AlertRule instances (or their dict form) evaluated over the
    # registry at every snapshot tick; rule levels are gauges and
    # transitions go to alert_sink + the flight recorder. Requires
    # snapshot_interval_s > 0 to evaluate during the run (a final
    # evaluation always happens at job close).
    alert_sink: Optional[object] = None
    # callable(transition_dict) invoked on every health level change
    # (e.g. print, or append to an alerts file); exceptions swallowed.

    # -- live scrape endpoint (obs/serve.py) --------------------------------
    serve_port: Optional[int] = None
    # None (default): no endpoint. >= 0: a background http.server daemon
    # thread serves GET /metrics (Prometheus text), /healthz (HealthEngine
    # levels; HTTP 503 while any rule is CRIT) and /snapshot.json for the
    # life of the job; 0 binds an ephemeral port (JobObs.server.port).
    serve_host: str = "127.0.0.1"
    # bind address for the endpoint; loopback by default — exposing it
    # beyond the host is an explicit decision

    # -- crash-dump flight recorder (obs/flightrecorder.py) -----------------
    flight_recorder: bool = True      # record runtime incidents (when
                                      # obs is enabled)
    flight_ring_size: int = 512       # bounded event ring (O(1)/event)
    flight_dump_path: str = ""        # where the postmortem JSON goes on
                                      # failure; "" = <cwd>/tpustream-flight-
                                      # <pid>.json
    flight_watermark_jump_ms: int = 60_000
    # watermark advances larger than this (per observation) are recorded
    # as watermark_jump events — the classic "someone replayed old data /
    # a partition went idle" postmortem breadcrumb

    # -- time series, profiling (obs/timeseries.py, obs/profiler.py) --------
    timeseries_ring: int = 512
    # bounded (timestamp, value) history behind every registry series:
    # windowed rate()/delta()/mean()/quantile() from inside the job.
    # 0 disables history entirely (point-in-time registry, pre-PR8).
    timeseries_digest: int = 64
    # t-digest-style centroids a sample series folds evicted points
    # into, so long-window quantiles stay approximately right after the
    # raw ring has turned over
    histogram_reservoir: int = 4096
    # raw-sample bound for unbounded (max_samples=0) histograms via
    # reservoir sampling — count/sum stay exact, the retained samples
    # become a uniform subsample of the whole run. 0 = truly unbounded.
    profile_window_s: float = 30.0
    # lookback window for the continuous pipeline profiler's per-stage
    # shares / binding stage (the "profile" snapshot section)

    # -- dataflow conservation ledger (obs/ledger.py) -----------------------
    ledger: Optional[bool] = None
    # per-edge record conservation accounting: source admission, chained
    # hand-offs, terminal/side sink fan-out, retained-sink contents —
    # residuals mint ledger_conservation_residual{edge} gauges, and the
    # first nonzero residual latches ledger_violations_total + a
    # ledger_violation breadcrumb behind an auto-installed CRIT health
    # rule. None (default) = auto: on whenever obs is enabled; the
    # ledger lives on the registry so True with obs off is dead config
    # (analyzer rule TSM051). Forced off under multi-host execution.
    ledger_digests: bool = True
    # fold every emitted row into a per-sink rolling sha256; checkpoints
    # carry the (count, digest) anchors and supervised restores
    # re-derive + verify them (ledger_restore_digest_mismatch). One hash
    # update per emitted row — turn off to keep counting-only ledgers.

    # -- adaptive pipeline controller (runtime/controller.py) ---------------
    adaptive: bool = False
    # master switch, STRICTLY off by default: at snapshot ticks an
    # AdaptiveController hill-climbs async_depth/fetch_group/h2d_depth
    # (the barrier-safe overlap depths — never semantics-bearing config)
    # toward higher windowed ingest rate under the p99 bound below.
    # Changes apply only at drained barriers; output bytes never change.
    # Forced off under multi-host execution.
    adaptive_bounds: Optional[dict] = None
    # {knob: (lo, hi)} per-knob search bounds; None = controller
    # defaults (runtime/controller.py DEFAULT_BOUNDS). Unknown knob
    # names are ignored — the knob set is closed.
    adaptive_cooldown_ticks: int = 2
    # settle ticks between moves: each probe is judged against a
    # baseline measured after the previous change took effect
    adaptive_hysteresis: float = 0.05
    # a probe is kept only if the objective improved by more than this
    # fraction — measurement noise can't walk the knobs
    adaptive_p99_ms: float = 300.0
    # latency guard (ROADMAP's "sustainable-rate p99 under 300 ms"):
    # probes that push e2e p99 past this revert; a steady-state breach
    # steps every depth down one notch

    def replace(self, **kw) -> "ObsConfig":
        import dataclasses

        return dataclasses.replace(self, **kw)


@dataclass
class StreamConfig:
    # -- batching -----------------------------------------------------------
    batch_size: int = 8192            # records per device step (static shape)
    max_batch_delay_ms: float = 5.0   # max host-side wait to fill a batch

    # -- keyed state --------------------------------------------------------
    key_capacity: int = 1024          # INITIAL dense keyed-state slots;
                                      # grows 2x (one recompile) when the
                                      # distinct-key count passes it
                                      # (bench configs raise to >=1<<20)

    # -- windows ------------------------------------------------------------
    pane_ring_slack: int = 16         # extra pane slots beyond (size+delay)/pane
    max_fires_per_step: Optional[int] = None  # default: pane ring length
    process_buffer_capacity: int = 128  # per-(key,pane) element buffer for
                                        # full-window process() functions
    session_extra_panes: int = 48       # extra ring slots for session windows:
                                        # bounds supported session length at
                                        # ~(slack + extra) * gap

    # -- emission / alerts --------------------------------------------------
    alert_capacity: int = 65536       # compacted device->host alert slots/step
    fire_capacity: Optional[int] = None  # session windows: fired
                                         # (key, session) rows composed per
                                         # step before the post-chain filter
                                         # (None = key_capacity). Count
                                         # process() windows: bound on the
                                         # per-step [fires, size] element
                                         # matrices (None = batch_size,
                                         # exact). Time windows compose
                                         # fires densely and don't use
                                         # this. Overflow beyond either
                                         # capacity is counted in
                                         # state["alert_overflow"].

    # -- numerics -----------------------------------------------------------
    # float64 reproduces the reference's Java-double golden outputs exactly
    # (chapter2/README.md:162). TPU benchmark configs use float32/int32.
    value_dtype: str = "float64"
    acc_dtype: str = "float64"
    ts_dtype: str = "int64"

    # -- parallelism --------------------------------------------------------
    parallelism: int = 1              # number of mesh shards (devices)
    print_parallelism: Optional[int] = None  # subtask count for the `n>`
                                             # print prefix; None = parallelism
                                             # (prefix omitted when it is 1,
                                             # matching Flink)
    exchange_capacity_factor: Optional[float] = None
    # per-destination all_to_all slots = factor * local_batch / shards.
    # None = full local batch per destination: records can NEVER be
    # dropped by the exchange regardless of key skew (Flink semantics).
    # Set a factor to shrink send buffers when keys are known-uniform;
    # overflow is then counted in state["exchange_overflow"].

    # -- failure policy -----------------------------------------------------
    restart_strategy: Optional[object] = None
    # A runtime.supervisor.RestartStrategy (fixed_delay / failure_rate /
    # no_restart — Flink 1.8's RestartStrategies surface, also settable
    # via StreamExecutionEnvironment.set_restart_strategy). None
    # (default) = unsupervised: the first failure propagates, exactly
    # as before this knob existed. Set, execute_job runs under
    # runtime/supervisor.py: failures consult the strategy and a
    # restart rebuilds the runner chain and resumes exactly-once from
    # the latest valid checkpoint (or from scratch when none exists).
    # Requires a replayable source (ReplaySource family).

    dead_letter: bool = False
    # Data-plane graceful degradation: lines that fail parsing or
    # timestamp extraction are quarantined to env.dead_letters (the
    # dead-letter output, (line, error) pairs) and counted in
    # records_quarantined instead of failing the job. Default False
    # preserves fail-fast semantics. Quarantine probing re-runs the
    # host parse per line on a failed batch — the slow path costs only
    # on batches that actually contain poison.
    dead_letter_capacity: int = 65536
    # retained dead-letter records; past it lines are dropped after
    # counting (the counter stays exact)

    sink_retries: int = 0
    # Sink emit failures retry this many times with capped exponential
    # backoff before escalating to the supervisor (0 = escalate
    # immediately). Applies per emit call.
    sink_retry_base_ms: float = 10.0
    sink_retry_max_ms: float = 1000.0
    # backoff delay: min(base * 2^attempt, max) milliseconds

    strict_overflow: bool = True
    # When True (default) the job FAILS (RuntimeError at end of stream)
    # if any lossy counter went nonzero: exchange_overflow (keyBy shuffle
    # dropped records — Flink never does), buffer_overflow (a full-window
    # process() buffer truncated, which would silently corrupt e.g. a
    # median), alert_overflow, or evicted_unfired. False keeps the
    # counters observable in JobResult.summary() without failing.

    # -- host<->device pipeline --------------------------------------------
    async_depth: int = 2
    # Steps allowed in flight before the executor fetches a step's
    # emissions: 1 = fully synchronous (fetch right after enqueue);
    # 2 (default) = double-buffered — batch N+1 is parsed and enqueued
    # while N's emissions cross PCIe, so host, transfer, and device
    # compute overlap (SURVEY.md §7 "double-buffered async dispatch").
    # Sink output order is unchanged; only its wall-clock moment shifts.
    # Programs whose emissions are evaluated against live device state
    # (full-window process()) force depth 1. Raise past 2 when the
    # host-device round trip exceeds a step's device time.

    fetch_group: int = 1
    # How many in-flight steps' emission-COUNT scalars fetch in ONE
    # device_get round trip. 1 (default) fetches per step — right for
    # PCIe hosts where a round trip is microseconds and per-step counts
    # let the executor skip batch-sized emission buffers immediately.
    # Where a host-device round trip is slow, the per-step scalar
    # fetch becomes the binding full-path stage; grouping G steps
    # amortizes that round trip G-ways. No emission dispatches later
    # than at G=1 — the oldest in-flight entry finishes at the same
    # feed either way and the rest finish earlier; the costs are a
    # longer blocking wait per finish call and an effective in-flight
    # depth that oscillates by G. Capped by what is actually in
    # flight, so paced sources (which drain synchronously) are
    # unaffected. Results are byte-identical either way — only
    # wall-clock dispatch time shifts.
    # The executor clamps the EFFECTIVE group to async_depth - 1 (at
    # least 1): a group equal to the full in-flight window would drain
    # the pipeline empty on every fetch, silently serializing dispatch
    # against the round trip it was meant to amortize (ADVICE r5). Ask
    # for a bigger group by raising async_depth alongside fetch_group.

    ingest_lanes: int = 1
    # Sharded host ingestion (runtime/ingest.py): > 1 splits source
    # frames round-robin across N lane worker PROCESSES, each running
    # the compiled columnar parse plan (hostparse + native/_fastparse)
    # over a shared-memory ring of length-framed batches and shipping
    # transport-packed columns back. The merge point consumes frames in
    # strict sequence order and reconciles per-lane intern tables and
    # demotion chains, so output stays byte-identical to the default
    # single-lane path and exactly-once recovery is unchanged (the
    # source cursor replays un-merged frames). 1 (default) = today's
    # inline host stage; no worker, no ring, no extra cost. Forced to 1
    # under multi-host execution, when the job's host stage has no
    # native columnar plan (fallback map, punctuated watermarks,
    # computed keys), or when the source is not splittable — each with
    # a flight breadcrumb (analyzer rule TSM016 flags these ahead of
    # time). Lanes beyond the host's core count add scheduling overhead
    # without parse throughput (TSM016 WARN).

    ingest_lane_restarts: int = 2
    # Lane supervision budget (runtime/ingest.py): how many times a dead
    # ingest lane worker (nonzero exit, premature clean exit before EOS,
    # or heartbeat stall) is respawned IN PLACE, per lane, before the
    # lane folds out of the round-robin permanently. Recovery is local:
    # the producer retains every raw frame until its seq is merged, so a
    # dead lane's un-merged frames re-parse via the inline host route at
    # their exact sequence positions — output stays byte-identical and
    # the job never restarts (job_restarts_total stays 0; the lane-level
    # ingest_lane_restarts_total{lane=...} counter ticks instead). 0 =
    # fold immediately on first death. All lanes folded degrades the
    # whole plane to the inline path (ingest_degraded breadcrumb): the
    # job keeps running slower instead of dying.

    ingest_lane_stall_limit_ms: float = 5000.0
    # Heartbeat stall detection for lane workers: each worker stamps a
    # shared monotonic timestamp per frame (and while idle); a lane with
    # work outstanding whose heartbeat is older than this limit is
    # declared hung and recovered exactly like a crashed one (SIGTERM,
    # frames re-routed inline, bounded respawn per ingest_lane_restarts).
    # 0 disables heartbeat detection — a hung worker then surfaces via
    # the plane-level StallWatchdog as a typed IngestStallError the
    # supervisor restarts-with-cause (extra["ingest_watchdog_limit_ms"]
    # tunes that escalation deadline; default max(30s, 4x this limit)).
    # Set comfortably above the slowest legitimate frame parse: a limit
    # below ~2x the typical frame deadline recovers healthy-but-slow
    # lanes in a loop (analyzer rule TSM017 WARNs).

    parse_ahead: int = 0
    # Source+parse pipelining depth: >0 moves the host stage (source
    # read, line skip on resume, parse + intern) onto its own thread
    # with a bounded hand-off queue, overlapping batch N+1's parse with
    # batch N's H2D/device work — the reference's threading model
    # (Flink's source runs as its own operator thread; SURVEY.md §3.1).
    # 0 (default) keeps the single-threaded loop. Single-process only
    # (multi-host keeps the deterministic inline path). Safe with
    # checkpoint/resume: interning is replay-deterministic, so a parser
    # running <= parse_ahead batches ahead of the fed position only
    # pre-interns ids a resumed run would re-derive identically.

    h2d_compress: bool = True
    # Lossless host->device transfer compression: int64 record columns
    # and timestamps ship as int32 deltas against a per-batch base and
    # re-expand on device. int64 columns dominate batch wire bytes
    # (timestamps, epoch fields, counters), so this roughly halves H2D
    # traffic on the host link. A column whose per-batch span exceeds
    # int32 falls back to raw permanently (one recompile).

    packed_wire: bool = True
    # Narrow packed wire format on top of h2d_compress: each H2D column
    # ships in the narrowest dtype the batch's values admit and widens
    # back on device. int64 deltas start at uint16 (d16) before falling
    # back to the int32 deltas above; float64 columns ship as float32
    # while every valid value round-trips exactly; interned-string id
    # columns ship as int16 while ids fit; bool columns and the valid
    # mask ship bit-packed (8 rows/byte). Demotions are sticky and
    # per-column (at most one recompile each, same policy as
    # h2d_compress), so outputs stay byte-identical to packed_wire=False.
    # Multi-host runs keep row-width packing but skip bit-packing (the
    # per-process shard split assumes one row per wire element).

    h2d_depth: int = 2
    # Upload-side pipeline depth: how many packed batches may be staged
    # on the device ahead of the step that consumes them. 1 = the
    # classic path (the transfer rides the step call). 2 (default) =
    # double-buffered H2D: batch N+1's device_put is issued before batch
    # N's step group fetch blocks, so its transfer crosses the wire
    # while the host waits on N's emission counts. Staged batches are
    # flushed at every pipeline barrier (checkpoint, rule update, key
    # growth, paced-source idle, end of stream), so checkpoint/recovery
    # semantics and output bytes are unchanged — only wall-clock overlap
    # shifts. Forced to 1 under multi-host, for programs whose
    # emissions reference live state, and when max_fires_per_step
    # paces the step loop.

    compaction_capacity: int = 4096
    # Device-side output compaction: each mask-carrying emission stream
    # gets a compiled compaction stage that gathers its (sparse) emitted
    # rows into a fixed [compaction_capacity] buffer in emission order,
    # so fetch pulls count + compacted rows instead of full [batch_size]
    # outputs. A step whose per-stream count exceeds the capacity spills
    # to the classic full fetch (flight breadcrumb + compaction_spills
    # counter) — semantics are exact at any alert density, the capacity
    # only tunes wire bytes. 0 disables the compaction stage entirely.
    # Single-chip only: on a multi-device mesh the compact gather
    # inserts a per-step all-gather whose rendezvous cost dwarfs the
    # fetch saving, and multi-host fetch needs the per-process dense
    # buffers for the chain merge — both keep the full path.

    # -- pre-flight analysis (tpustream/analysis) ---------------------------
    strict_analysis: bool = False
    # True: execute() runs the static plan analyzer BEFORE planning or
    # compiling anything, and any ERROR finding raises PlanAnalysisError
    # (the job never traces). False (default): analysis still runs when
    # obs is enabled — findings become flight breadcrumbs and
    # analysis_findings_total{code=...} counters — but never blocks.
    # docs/analysis.md catalogs the TSM0xx rules.

    # -- observability ------------------------------------------------------
    obs: ObsConfig = field(default_factory=ObsConfig)

    # -- misc ---------------------------------------------------------------
    checkpoint_dir: Optional[str] = None
    checkpoint_interval_batches: int = 0  # 0 = disabled
    # Retention tiers (runtime/checkpoint.py): keep the N newest
    # snapshots; additionally every Mth snapshot (by write ordinal) is
    # durable and survives pruning (0 = no durable tier). Savepoints
    # (env.savepoint()) are always pinned regardless of these.
    checkpoint_keep: int = 3
    checkpoint_keep_every: int = 0
    # Async snapshotting: True hands each captured cut to a single
    # background writer thread (CheckpointPlane) so the barrier only
    # pays capture; False writes synchronously on the hot path. The
    # in-flight budget bounds queued cuts (a barrier arriving while the
    # queue is full waits — counted as a stall).
    checkpoint_async: bool = True
    checkpoint_async_inflight: int = 1
    # Incremental snapshots: True writes chunked manifests (per-leaf
    # content-hashed chunk files; unchanged leaves re-use earlier
    # chunks, so steady-state bytes scale with churn). False writes
    # self-contained inline snapshots (the pre-v12 payload shape).
    checkpoint_incremental: bool = True
    # Restore drills: > 0 dry-restores the nominal newest snapshot
    # every this-many seconds in-process (format + chunk-chain walk +
    # layout audit + ledger anchor re-derivation) so bit-rot or a
    # half-GC'd chain becomes a WARN/CRIT health transition before a
    # crash needs the snapshot. 0 (default) disables drills.
    restore_drill_interval_s: float = 0.0
    collect_metrics: bool = True

    extra: dict = field(default_factory=dict)

    def replace(self, **kw) -> "StreamConfig":
        import dataclasses

        return dataclasses.replace(self, **kw)

    def resolve(self) -> "tuple[StreamConfig, list]":
        """Effective-config resolution: cross-knob constraints applied
        once, at submission, instead of silently at runtime.

        Returns ``(resolved_cfg, notes)`` where each note is a dict
        ``{knob, requested, effective, reason}``; the executor records
        one ``config_clamped`` flight breadcrumb per note. Currently one
        constraint: ``fetch_group`` is clamped to ``async_depth - 1``
        (at least 1) — a group spanning the full in-flight window would
        drain the pipeline empty on every grouped fetch, serializing
        dispatch against the round trip it exists to amortize (ADVICE
        r5). The runtime keeps its live per-step clamp too (the
        adaptive controller can move async_depth under a running job).
        """
        notes: list = []
        limit = max(1, self.async_depth - 1)
        eff = max(1, min(self.fetch_group, limit))
        cfg = self
        if eff != self.fetch_group:
            notes.append({
                "knob": "fetch_group",
                "requested": self.fetch_group,
                "effective": eff,
                "reason": f"clamped to async_depth-1={limit}: a "
                          "full-window fetch group drains the pipeline "
                          "on every grouped fetch",
            })
            cfg = self.replace(fetch_group=eff)
        if self.ingest_lanes < 1:
            notes.append({
                "knob": "ingest_lanes",
                "requested": self.ingest_lanes,
                "effective": 1,
                "reason": "ingest_lanes must be >= 1; 1 is the inline "
                          "single-lane host stage",
            })
            cfg = cfg.replace(ingest_lanes=1)
        if self.ingest_lane_restarts < 0:
            notes.append({
                "knob": "ingest_lane_restarts",
                "requested": self.ingest_lane_restarts,
                "effective": 0,
                "reason": "ingest_lane_restarts must be >= 0; 0 folds a "
                          "lane out on its first death",
            })
            cfg = cfg.replace(ingest_lane_restarts=0)
        if self.ingest_lane_stall_limit_ms < 0:
            notes.append({
                "knob": "ingest_lane_stall_limit_ms",
                "requested": self.ingest_lane_stall_limit_ms,
                "effective": 0.0,
                "reason": "ingest_lane_stall_limit_ms must be >= 0; 0 "
                          "disables heartbeat stall detection",
            })
            cfg = cfg.replace(ingest_lane_stall_limit_ms=0.0)
        if self.checkpoint_keep < 1:
            notes.append({
                "knob": "checkpoint_keep",
                "requested": self.checkpoint_keep,
                "effective": 1,
                "reason": "checkpoint_keep must be >= 1; the newest "
                          "snapshot is the recovery floor",
            })
            cfg = cfg.replace(checkpoint_keep=1)
        if self.checkpoint_keep_every < 0:
            notes.append({
                "knob": "checkpoint_keep_every",
                "requested": self.checkpoint_keep_every,
                "effective": 0,
                "reason": "checkpoint_keep_every must be >= 0; 0 "
                          "disables the durable tier",
            })
            cfg = cfg.replace(checkpoint_keep_every=0)
        if self.checkpoint_async_inflight < 1:
            notes.append({
                "knob": "checkpoint_async_inflight",
                "requested": self.checkpoint_async_inflight,
                "effective": 1,
                "reason": "checkpoint_async_inflight must be >= 1; the "
                          "writer needs at least one queue slot",
            })
            cfg = cfg.replace(checkpoint_async_inflight=1)
        if self.restore_drill_interval_s < 0:
            notes.append({
                "knob": "restore_drill_interval_s",
                "requested": self.restore_drill_interval_s,
                "effective": 0.0,
                "reason": "restore_drill_interval_s must be >= 0; 0 "
                          "disables restore drills",
            })
            cfg = cfg.replace(restore_drill_interval_s=0.0)
        return cfg, notes
