"""Job execution: host pipeline driving the compiled device program.

The run loop realizes SURVEY.md §7's design stance: the host turns the
byte stream into fixed-size structure-of-arrays batches; one jitted XLA
program advances ``(state, batch) -> (state', emissions)``; sinks format
compacted emissions. Processing-time fires are driven by a monotone host
clock (virtual under the deterministic replay source), event-time fires
purely by the data-derived watermark — so every golden transcript from
the reference READMEs replays exactly (SURVEY.md §4).
"""

from __future__ import annotations

import itertools
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..api.functions import as_callable
from ..api.watermarks import (
    MAX_WATERMARK,
    AssignerWithPunctuatedWatermarks,
)
from ..config import StreamConfig
from ..hostparse import PlanEvaluator, run_fallback_map
from ..records import (
    STR,
    Batch,
    Column,
    DerivedKeyTable,
    StringTable,
    f64_from_image,
    f64_image,
)
from ..api.timeapi import TimeCharacteristic
from ..obs.tracing import NULL_TRACER
from .metrics import Metrics, Stopwatch
from .plan import JobPlan, build_plan_chain
from .sinks import (
    CollectSink,
    EmissionFormatter,
    FnSink,
    LedgerSink,
    PrintSink,
    RetryingSink,
    iter_rows,
)
from .sources import SourceBatch
from .step import LONG_MIN, RULE_VERSION_KEY, RULES_KEY, build_program


class HostStage:
    """Raw lines -> columnar Batch (parse, timestamps, raw-stage ops)."""

    def __init__(self, plan: JobPlan, cfg: StreamConfig):
        self.plan = plan
        self.cfg = cfg
        self._ts_eval: Optional[PlanEvaluator] = None
        self._map_evals: Dict[int, PlanEvaluator] = {}
        self._raw_eval = None       # combined [ts?]+outputs native parser
        self._raw_eval_built = False
        self._raw_has_ts = False
        # conservation-ledger source terms (obs/ledger.py): when the
        # executor arms this dict, process() commits its filter-drop /
        # flat_map fan counts here ON SUCCESS (an aborted parse commits
        # nothing, so quarantine reprocessing can't double-count);
        # account_source drains it per batch. None = ledger off.
        self.ledger_counts: Optional[dict] = None
        # step tracer (obs/tracing.py) for the native parse's sub-spans
        # and the intern_new_ids_total counter; the executor installs
        # the job's (null twins when obs is off)
        self.tracer = None
        self.new_ids = None
        if plan.ts_expr is not None:
            self._ts_eval = PlanEvaluator([plan.ts_expr], [None])

    def _build_raw_eval(self):
        """One native parse pass computing the event-time column (when
        assigned) AND the parse map's output columns straight from a raw
        byte buffer — the ingest path that never touches per-line Python
        objects. None when the job's host stage can't take it (fallback
        map, raw-stage filter/flat_map, punctuated watermarks)."""
        plan = self.plan
        if plan.synthetic_key:
            # the derived-key column is an arbitrary Python callable —
            # no native lane
            return None
        if len(plan.host_ops) != 1:
            return None
        hop = plan.host_ops[0]
        if hop.op != "map" or hop.plan is None or hop.plan.fallback_fn is not None:
            return None
        if plan.ts_assigner is not None and plan.ts_expr is None:
            return None
        if isinstance(plan.ts_assigner, AssignerWithPunctuatedWatermarks):
            return None
        exprs, tbls = [], []
        self._raw_has_ts = plan.ts_expr is not None
        if self._raw_has_ts:
            exprs.append(plan.ts_expr)
            tbls.append(None)
        exprs.extend(hop.plan.outputs)
        tbls.extend(
            t if k == STR else None
            for k, t in zip(plan.record_kinds, plan.tables)
        )
        ev = PlanEvaluator(exprs, tbls)
        return ev if ev._native is not None else None

    def process_raw(self, raw: bytes, n: int, proc_ts: np.ndarray,
                    step: int = -1):
        """Raw-buffer twin of :meth:`process`. Returns (Batch, wm_hint)
        or (None, None) when the native lane can't parse this batch —
        the caller then decodes and takes the line path. ``step`` is the
        id the batch's ``parse.*`` sub-spans carry."""
        if not n:
            return None, None
        if not self._raw_eval_built:
            self._raw_eval = self._build_raw_eval()
            self._raw_eval_built = True
        if self._raw_eval is None:
            return None, None
        cols = self._raw_eval.parse_bytes(raw, n, tracer=self.tracer,
                                          step=step, new_ids=self.new_ids)
        if cols is None:
            return None, None
        ts = None
        if self._raw_has_ts:
            ts = np.asarray(cols[0], dtype=np.int64)
            cols = cols[1:]
        plan = self.plan
        columns = [
            Column(k, c, t)
            for k, c, t in zip(plan.record_kinds, cols, plan.tables)
        ]
        return Batch(n, columns, ts=ts, proc_ts=proc_ts), None

    @staticmethod
    def _append_synthetic_schema(plan) -> None:
        """Adaptive parse schemas resolve on the first batch; the
        computed-KeySelector column appends right after (plan-time
        resolution appends it in build_plan instead)."""
        from ..records import DerivedKeyTable

        if plan.synthetic_key:
            plan.record_kinds.append(STR)
            plan.tables.append(DerivedKeyTable())

    def _derived_key_col(self, cols, n: int) -> np.ndarray:
        return derive_key_column(self.plan, cols, n)

    def _timestamps(self, lines: List[str]) -> Optional[np.ndarray]:
        plan = self.plan
        if plan.ts_assigner is None:
            return None
        if self._ts_eval is not None:
            (ts,) = self._ts_eval(lines)
            return np.asarray(ts, dtype=np.int64)
        extract = plan.ts_assigner.extract_timestamp
        return np.asarray([extract(l) for l in lines], dtype=np.int64)

    def _punctuated_wm(self, lines: List[str], ts: np.ndarray) -> Optional[int]:
        a = self.plan.ts_assigner
        if not isinstance(a, AssignerWithPunctuatedWatermarks):
            return None
        wm = None
        for line, t in zip(lines, ts):
            w = a.check_and_get_next_watermark(line, int(t))
            if w is not None:
                wm = w.timestamp if wm is None else max(wm, w.timestamp)
        return wm

    def _ledger_commit(self, dropped: int, fm_in: int, fm_out: int) -> None:
        c = self.ledger_counts
        if c is not None:
            c["dropped"] += dropped
            c["fm_in"] += fm_in
            c["fm_out"] += fm_out

    def process(self, lines: List[str], proc_ts: np.ndarray):
        """Returns (Batch, wm_hint) — Batch is None for empty input."""
        plan = self.plan
        if not lines:
            return None, None
        ts = self._timestamps(lines)
        wm_hint = self._punctuated_wm(lines, ts) if ts is not None else None

        # ledger source-edge deltas, committed only on a successful
        # return — a parse exception after a filter/flat_map must not
        # count those ops twice when quarantine reprocesses the batch
        l_dropped = l_fm_in = l_fm_out = 0
        cols: Optional[List[np.ndarray]] = None
        for i, hop in enumerate(plan.host_ops):
            if hop.op == "filter":
                fn = as_callable(hop.fn, "filter")
                keep = [bool(fn(l)) for l in lines]
                lines = [l for l, k in zip(lines, keep) if k]
                l_dropped += len(keep) - len(lines)
                sel = np.asarray(keep, dtype=bool)
                proc_ts = proc_ts[sel]
                if ts is not None:
                    ts = ts[sel]
                if not lines:
                    self._ledger_commit(l_dropped, l_fm_in, l_fm_out)
                    return None, wm_hint
                continue
            if hop.op == "flat_map":
                fn = as_callable(hop.fn, "flat_map")
                l_fm_in += len(lines)
                new_lines, new_proc, new_ts = [], [], []
                for j, l in enumerate(lines):
                    outs = list(fn(l))
                    new_lines.extend(outs)
                    new_proc.extend([proc_ts[j]] * len(outs))
                    if ts is not None:
                        new_ts.extend([ts[j]] * len(outs))
                lines = new_lines
                l_fm_out += len(lines)
                proc_ts = np.asarray(new_proc, dtype=np.int64)
                ts = np.asarray(new_ts, dtype=np.int64) if ts is not None else None
                if not lines:
                    self._ledger_commit(l_dropped, l_fm_in, l_fm_out)
                    return None, wm_hint
                continue
            # map: symbolic fast path or per-record fallback
            if hop.plan is not None and hop.plan.fallback_fn is None:
                ev = self._map_evals.get(i)
                if ev is None:
                    tables = [
                        t if k == STR else None
                        for k, t in zip(plan.record_kinds, plan.tables)
                    ]
                    ev = PlanEvaluator(hop.plan.outputs, tables)
                    self._map_evals[i] = ev
                cols = ev(lines)
            else:
                fb = hop.plan.fallback_fn if hop.plan else as_callable(hop.fn, "map")
                cols, kinds = run_fallback_map(fb, lines, plan.tables)
                if not plan.record_kinds:
                    plan.record_kinds.extend(kinds)
                    self._append_synthetic_schema(plan)
            break  # planner guarantees ops after the parse map are device-side

        if cols is None:
            # stream stays raw strings: one interned STR column
            if not plan.record_kinds:
                plan.record_kinds.append(STR)
                plan.tables.append(StringTable())
                self._append_synthetic_schema(plan)
            cols = [plan.tables[0].intern_many(lines)]

        if plan.synthetic_key:
            cols = list(cols) + [self._derived_key_col(cols, len(lines))]

        columns = [
            Column(k, c, t)
            for k, c, t in zip(plan.record_kinds, cols, plan.tables)
        ]
        self._ledger_commit(l_dropped, l_fm_in, l_fm_out)
        return (
            Batch(len(lines), columns, ts=ts, proc_ts=proc_ts),
            wm_hint,
        )


def _allgather_rows(arrays: List[np.ndarray]) -> List[np.ndarray]:
    """Concatenate each array's rows across ALL processes (row counts
    may differ per process: gather the counts, pad to the max, gather,
    trim). Host-level DCN collective — used only on the chain hand-off,
    at alert scale, never on the per-record path."""
    from jax.experimental import multihost_utils as mh

    counts = mh.process_allgather(
        np.asarray([arrays[0].shape[0]], np.int64)
    ).reshape(-1)
    mx = int(counts.max())
    if not mx:
        # globally empty step (the common case: most steps fire
        # nothing): mx is SPMD-identical, so every process skips the
        # data gathers together — collective counts stay aligned
        return arrays
    out = []
    for a in arrays:
        pad = np.zeros((mx - a.shape[0],) + a.shape[1:], a.dtype)
        g = mh.process_allgather(np.concatenate([a, pad]))
        out.append(
            np.concatenate(
                [g[p, : int(counts[p])] for p in range(g.shape[0])]
            )
        )
    return out


def derive_key_column(plan, cols, n: int) -> np.ndarray:
    """Computed-KeySelector fallback: reconstruct each visible record
    from its columns, run the user selector, intern the result into the
    plan's trailing DerivedKeyTable (per-record Python — the
    correctness lane; field projections take the symbolic path and
    never come here). Used by the host parse stage and by the chain
    glue when a CHAIN stage keys by a computed selector.

    Filters between the parse map (or re-key hand-off) and the
    computed key_by run on device AFTER this column is built — but
    Flink's getKey never sees a filtered-out record, and a partial
    selector (``100 // r.f2``) must not crash on one. So the same
    filter predicates evaluate here, host-side, and dropped rows get
    the table's reserved PLACEHOLDER_ID (the device mask excludes them
    from all keyed work; the reserved id guarantees that even a
    host/device filter disagreement cannot alias a real key's
    state)."""
    from ..api.tuples import make_tuple

    kinds = plan.record_kinds[:-1]
    tables = plan.tables[:-1]
    fn = plan.derived_key_fn  # already resolved to a callable
    filters = [
        as_callable(f, "filter")
        for op, f in plan.device_pre
        if op == "filter"
    ]
    vals = np.full(n, DerivedKeyTable.PLACEHOLDER_ID, dtype=np.int32)
    for j in range(n):
        fields = []
        for k, t, c in zip(kinds, tables, cols):
            v = c[j]
            if k == STR:
                fields.append(t.lookup(int(v)))
            elif k == "f64":
                fields.append(float(v))
            elif k == "bool":
                fields.append(bool(v))
            else:
                fields.append(int(v))
        rec = fields[0] if len(fields) == 1 else make_tuple(*fields)
        if all(f(rec) for f in filters):
            vals[j] = plan.tables[-1].intern_value(fn(rec))
    return vals


def _row_fields(row) -> list:
    """Positional fields of a user-collected row (Tuple / tuple / scalar)."""
    from ..api.tuples import TupleBase

    return list(row) if isinstance(row, (TupleBase, tuple)) else [row]


def _infer_row_kinds(rows) -> List[str]:
    """Column kinds for user-collected rows, WIDENED across every row
    (any str -> STR; else any non-bool float/int mix -> F64; all bool ->
    BOOL; else I64)."""
    from ..records import BOOL, F64, I64

    fields = [_row_fields(r) for r in rows]
    arity = len(fields[0])
    for f in fields:
        if len(f) != arity:
            raise ValueError(
                f"chained process() stage collected rows of mixed arity "
                f"({arity} vs {len(f)}); emit one consistent shape"
            )
    kinds = []
    for i in range(arity):
        vs = [f[i] for f in fields]
        if any(isinstance(v, str) for v in vs):
            kinds.append(STR)
        elif all(isinstance(v, bool) for v in vs):
            kinds.append(BOOL)
        elif any(isinstance(v, float) for v in vs):
            kinds.append(F64)
        else:
            kinds.append(I64)
    return kinds


def _bind_ops(ops):
    """Pre-resolve (op, fn) pairs to callables for per-record replay."""
    return [(op, as_callable(fn, op)) for op, fn in ops]


def _apply_ops(bound_ops, item):
    """Run a map/filter tail over one record; (item, kept)."""
    for op, fn in bound_ops:
        if op == "map":
            item = fn(item)
        elif not fn(item):
            return item, False
    return item, True


def _apply_ops_many(bound_ops, rows, subtasks=None):
    """:func:`_apply_ops` over many rows: ``(kept items, their
    subtasks)``, the subtasks None where none were given."""
    items = []
    kept = [] if subtasks is not None else None
    for j, row in enumerate(rows):
        item, keep = _apply_ops(bound_ops, row)
        if keep:
            items.append(item)
            if kept is not None:
                kept.append(subtasks[j])
    return items, kept


class JobResult:
    def __init__(self, metrics: Metrics):
        self.metrics = metrics

    def summary(self) -> dict:
        return self.metrics.summary()


def _make_sinks(plan: JobPlan, cfg: StreamConfig):
    pp = cfg.print_parallelism if cfg.print_parallelism is not None else cfg.parallelism

    inj = cfg.extra.get("fault_injector") if cfg.extra else None
    fault = inj.check if inj is not None else None

    def build_sink(node):
        if node.op == "sink_print":
            sink = PrintSink(parallelism=pp)
        elif node.op == "sink_collect":
            sink = CollectSink(node.params["handle"])
        else:
            sink = FnSink(node.params["fn"])
        # transient-failure backoff (StreamConfig.sink_retries), and the
        # mount point for injected sink_emit faults — wrapped even at
        # retries=0 under injection so the fault fires on the real emit
        # path and escalates like a genuine sink error
        if cfg.sink_retries > 0 or fault is not None:
            sink = RetryingSink(
                sink,
                attempts=cfg.sink_retries,
                base_ms=cfg.sink_retry_base_ms,
                max_ms=cfg.sink_retry_max_ms,
                fault=fault,
            )
        return sink

    # (host-side branch ops, sink) per main branch — ops run over the
    # compacted emissions (alert-scale), mirroring the reference's
    # stream fan-out where several consumers share one upstream.
    # Callables pre-bind here, off the per-record path.
    sinks = [
        (_bind_ops(branch.ops), build_sink(branch.sink_node))
        for branch in plan.branches
    ]
    side = {}
    for so in plan.side_outputs:
        side[so.tag.id] = (_bind_ops(so.ops), build_sink(so.sink_node))
    return sinks, side


def _ledger_contents(sink):
    """(contents_fn, persistent) for a sink's conservation-ledger
    account (obs/ledger.py). ``contents_fn`` exposes the retained row
    list a digest can be re-derived from; ``persistent`` marks
    env-owned contents that outlive a restart attempt — only those are
    verified against restored checkpoint anchors (a PrintSink's line
    buffer is rebuilt empty each attempt)."""
    if isinstance(sink, RetryingSink):
        sink = sink.inner
    if isinstance(sink, CollectSink):
        return (lambda s=sink: s.handle.items), True
    if isinstance(sink, PrintSink):
        return (lambda s=sink: s.lines), False
    return None, False


class _Inflight:
    """A dispatched step whose emissions have not reached the sinks yet.

    ``seq`` is the step's place in the runner's dispatch order. ``fetch``
    stays None until the step's fetch is planned: then it holds the
    streams still to fetch (count-sized slices launched on the device)
    and ``fetched`` the rows already on the host; ``wait_s`` is the
    time the host blocked planning it."""

    __slots__ = ("emissions", "counts", "compact", "t_batch", "markers",
                 "step", "seq", "fetch", "fetched", "wait_s")

    def __init__(self, emissions, counts, compact, t_batch, markers, step,
                 seq):
        self.emissions = emissions
        self.counts = counts
        self.compact = compact
        self.t_batch = t_batch
        self.markers = markers
        self.step = step
        self.seq = seq
        self.fetch: Optional[dict] = None
        self.fetched: dict = {}
        self.wait_s = 0.0

    def finished(self) -> bool:
        """Whether the step has finished on the device (its outputs,
        the count scalars among them, are ready): reading its counts
        does not wait."""
        return all(c.is_ready() for c in self.counts.values())


class Runner:
    """Feeds padded batches through the jitted program and fans emissions
    out to sinks."""

    def __init__(self, plan: JobPlan, cfg: StreamConfig, metrics: Metrics):
        self.plan = plan
        self.cfg = cfg
        self.metrics = metrics
        # seeded fault hook (tpustream/testing/faults.py): checked per
        # step for the device_step / exchange points; None in real runs
        _inj = cfg.extra.get("fault_injector") if cfg.extra else None
        self._fault = _inj.check if _inj is not None else None
        self.program = build_program(plan, cfg)
        self._inner_step = self.program.jitted_step()
        # per-operator observability scope: counters/histograms labelled
        # {job, operator} plus span minting. The null twin (obs disabled)
        # makes every obs call below a no-op attribute call.
        self.obs = metrics.job_obs.operator(self.program.operator_name)
        # step ids (obs/tracing.py): a fed batch brings the id its source
        # pull drew; a step with no pulled batch draws a fresh one here
        self._new_step = self.obs.tracer.new_step
        # why the NEXT _counted_step build happens (obs/compilation.py
        # causes); rebuild sites overwrite this before nulling self.step
        self._recompile_cause = "initial"
        self._compile_obs = None
        self._state_mem = None
        # H2D transfer compression: int64 columns and timestamps ship as
        # int32 deltas against a per-batch base scalar (lossless) and
        # re-expand on device — on the PCIe/host link these columns are
        # most of the wire bytes. A column whose per-batch span ever
        # exceeds int32 is demoted to raw permanently (one recompile).
        self._col_modes: Optional[tuple] = None
        self._ts_mode: Optional[str] = None
        self._valid_mode: Optional[str] = None
        self.step = None  # built on the first batch, when modes are known
        self.state = self.program.init_state()
        self.sinks, self.side_sinks = _make_sinks(plan, cfg)
        self.formatter = EmissionFormatter(
            self.program.out_kinds, self.program.out_tables,
            images=self.program.image_fields,
        )
        self.in_kinds = plan.record_kinds
        self._empty_cache = None
        # emission pipelining: up to (async_depth - 1) steps stay in
        # flight before their emissions are fetched, overlapping host
        # parse + H2D of the next batch with device compute and D2H of
        # the previous one. Programs that evaluate emissions against
        # live device state (full-window process()) must stay sync.
        depth = 1 if self.program.emissions_reference_state else cfg.async_depth
        self._max_inflight = max(0, depth - 1)
        self._inflight: List[_Inflight] = []
        self._dispatched = 0  # steps dispatched so far (_Inflight.seq)
        # end-to-end latency markers (obs/latency.py): markers ride the
        # inflight entries like data, so the source->edge age includes
        # real pipelining delay. Pending markers attach to the NEXT
        # step; recorded markers park in _marker_out until pump_chain
        # hands them downstream. Both stay empty unless the source
        # stamper is installed (obs on + latency_marker_interval_ms > 0).
        self._pending_markers: List = []
        self._marker_out: List = []
        self._flight = metrics.job_obs.flight
        # rows of the last firing step's 'main' prefix, one pow2 level
        # up: the least 'main' slice size (_plan_fetch) and the
        # speculative count+emission piggyback fetch (_speculative_main);
        # 0 until the first firing step establishes a scale
        self._prefix_hint = 0
        # -- multi-host (jax.distributed) SPMD --------------------------
        # every process runs this same executor over the same replayed
        # source; batch rows are globally sharded (each process donates
        # its contiguous slice), and each process dispatches only its
        # own shards' emissions to its local sinks — Flink's
        # task-manager-local sink semantics (chapter1/README.md:80-84's
        # n> prefixes, printed on whichever host owns the subtask)
        self._multiproc = jax.process_count() > 1
        mesh = getattr(self.program, "mesh", None)
        if self._multiproc:
            if mesh is None:
                raise NotImplementedError(
                    "multi-host execution needs a sharded program: set "
                    "StreamConfig.parallelism to the global device count"
                )
            # host-evaluated (process()) programs read state through a
            # local-shard fetcher: each process evaluates and emits its
            # OWN keys' fires (same ownership rule as device emissions)
            self.program._host_fetch = self._fetch_local
            if cfg.parallelism % jax.process_count():
                raise ValueError(
                    f"parallelism ({cfg.parallelism}) must divide evenly "
                    f"by the process count ({jax.process_count()})"
                )
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import AXIS

            self._data_sharding = NamedSharding(mesh, P(AXIS))
        if mesh is not None:
            from jax.sharding import NamedSharding

            # place the initial state onto the mesh as the step returns
            # it: leaves built host-local would not be addressable under
            # a multi-host SPMD step, and on one host an unplaced state
            # would miss the jit cache on the second step (its input
            # types would lack the mesh the first step's outputs carry)
            leaves, treedef = jax.tree_util.tree_flatten(self.state)
            spec_leaves = jax.tree_util.tree_leaves(
                self.program.state_specs(self.state),
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
            )
            placed = [
                jax.device_put(l, NamedSharding(mesh, s))
                for l, s in zip(leaves, spec_leaves)
            ]
            self.state = jax.tree_util.tree_unflatten(treedef, placed)
        # -- double-buffered H2D (StreamConfig.h2d_depth) -----------------
        # packed batches stage onto the device via an async device_put
        # up to _h2d_ahead steps before the step that consumes them, so
        # batch N+1's transfer crosses the wire while batch N's fetch
        # blocks the host. Forced synchronous (ahead = 0) under
        # multi-host (the gshard path IS the transfer), for programs
        # whose emissions read live state, and when max_fires_per_step
        # interleaves drain steps with fed batches (a staged batch would
        # run after drain steps that must follow it).
        stage_ok = (
            not self._multiproc
            and not self.program.emissions_reference_state
            and cfg.max_fires_per_step is None
        )
        self._h2d_ahead = max(0, cfg.h2d_depth - 1) if stage_ok else 0
        self._upload_q: List[tuple] = []
        self._h2d_sharding = None
        mesh = getattr(self.program, "mesh", None)
        if self._h2d_ahead and mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import AXIS

            # stage batch-shaped leaves already row-sharded so the jit
            # dispatch doesn't pay a reshard copy on the mesh
            self._h2d_sharding = NamedSharding(mesh, P(AXIS))
        # -- device-side output compaction (compaction_capacity) ----------
        # mask-carrying emission streams also return a gathered
        # [capacity] copy of their emitted rows + the row indices, so a
        # firing step fetches ~count rows instead of full [B] buffers.
        # Off under multi-host (the chain merge and _fetch_local need the
        # dense per-process buffers), for live-state programs, and on
        # multi-device meshes: gathering shard-local emission buffers
        # into the replicated compact leaves inserts an all-gather whose
        # per-step rendezvous cost dwarfs the fetch saving.
        self._compact_cap = (
            int(cfg.compaction_capacity)
            if cfg.compaction_capacity
            and cfg.parallelism <= 1
            and not self._multiproc
            and not self.program.emissions_reference_state
            else 0
        )
        self._spilled_streams: set = set()
        # wire-traffic series: bytes the executor actually moves each
        # way (null instruments when obs is off)
        self._h2d_bytes = self.obs.counter("h2d_bytes_total")
        self._fetch_bytes = self.obs.counter("fetch_bytes_total")
        self._spill_counter = self.obs.counter("compaction_spills")
        # fetch slice programs (one per sliced leaf), and those launched
        # while a later step was already dispatched: the chip runs
        # programs in launch order, so such a slice waits for that step
        self._slices_counter = self.obs.counter("fetch_slices_total")
        self._slices_behind = self.obs.counter(
            "fetch_slices_behind_step_total"
        )
        # rows handed to a sink in one emit_many call per branch (the
        # per-row fan-out of chained stages and the ledger counts none)
        self._rows_batched = self.obs.counter("emit_rows_batched_total")
        # float64 values packed as their int64 image (_pack "img" mode)
        self._images_packed = self.obs.counter("f64_ordered_values_total")
        self._compaction_gauge = self.obs.gauge("compaction_ratio")
        # chained stages: emissions feed the downstream runner as
        # columnar batches instead of the sinks (build_plan_chain).
        # Entry shape per step: single-host (cols, ts_or_None);
        # multi-host (cols, window_end, key) — the canonical sort and
        # ts extraction happen after the cross-process gather
        self.downstream: Optional["Runner"] = None
        self._chain_buf: List[tuple] = []
        # (item, ts, order) from process() fires; order is the
        # evaluation-loop position (used only for the multi-host merge)
        self._chain_rows: List[tuple] = []
        self._dispatch_seq = 0
        self._lazy_plans: List[JobPlan] = []  # stages after a process() stage
        self._chain_ts = False  # downstream chain contains event-time windows
        self.count_input = True
        # device counter values restored from a checkpoint (finalize
        # subtracts them so a resumed run reports since-resume numbers
        # and strict_overflow never fails on pre-snapshot loss)
        self._counter_baseline: Dict[str, int] = {}
        if self.obs.enabled:
            from ..obs.compilation import CompileObs
            from ..obs.memory import StateMemoryTracker

            # compile/recompile registry: _counted_step routes its jit
            # through a timed AOT build so wall time / cost analysis /
            # cause land in the registry before the first dispatch
            self._compile_obs = CompileObs(
                self.obs,
                self._flight,
                meta=getattr(
                    getattr(self.program, "pre_chain", None),
                    "describe",
                    dict,
                )(),
                op_scopes=(
                    metrics.job_obs.op_scopes
                    if self.obs.tracer.bridged else None
                ),
            )
            # HBM state accounting + key-cardinality/skew gauges
            self._state_mem = StateMemoryTracker(self)
            # pull-style backpressure gauge: chain hand-off rows parked
            # between pumps, read only at snapshot time
            self.obs.gauge("chain_buffer_entries").set_fn(
                lambda: len(self._chain_buf) + len(self._chain_rows)
            )
            # total pipeline depth in use: staged uploads + steps whose
            # emissions are still in flight (lazy; snapshot-time read)
            self.obs.gauge("pipeline_occupancy").set_fn(
                lambda: len(self._upload_q) + len(self._inflight)
            )
            if self.program.n_shards > 1:
                from ..parallel.exchange import exchange_capacity

                self.obs.gauge("exchange_capacity_rows").set(
                    exchange_capacity(
                        cfg.batch_size,
                        self.program.n_shards,
                        cfg.exchange_capacity_factor,
                    )
                )
            # every sink counts under TWO spellings kept in lockstep:
            # the legacy flat names (operator_sink{i}_emitted /
            # operator_side_sink{tag}_emitted, dashboards pin these)
            # and one uniform labeled family
            # operator_sink_emitted{sink="0"|"side:<tag>"} so ledger
            # edges and dashboards address main and side sinks alike
            from ..obs.registry import TwinCounter

            for i, (_, sink) in enumerate(self.sinks):
                sink.obs_counter = TwinCounter(
                    self.obs.counter(f"sink{i}_emitted"),
                    self.obs.scoped(sink=str(i)).counter(
                        "operator_sink_emitted"
                    ),
                )
                if isinstance(sink, RetryingSink):
                    sink.retry_counter = self.obs.counter(f"sink{i}_retries")
            for tag, (_, sink) in self.side_sinks.items():
                sink.obs_counter = TwinCounter(
                    self.obs.counter(f"side_sink{tag}_emitted"),
                    self.obs.scoped(sink=f"side:{tag}").counter(
                        "operator_sink_emitted"
                    ),
                )
                if isinstance(sink, RetryingSink):
                    sink.retry_counter = self.obs.counter(
                        f"side_sink{tag}_retries"
                    )
        # marker latency series: source->this-operator-edge, and (for
        # the terminal stage) source->each-sink. Null instruments when
        # obs is off — and markers never exist then anyway.
        self._e2e_hist = self.obs.histogram("e2e_latency_ms")
        self._sink_e2e = [
            self.obs.histogram(f"sink{i}_e2e_latency_ms")
            for i in range(len(self.sinks))
        ]
        # fleet runs: tenant-labeled e2e histograms, minted lazily per
        # label the round-robin stamper actually emits (bounded upstream
        # to top-K + "__other__" by the JobServer)
        self._tenant_e2e: Dict[str, object] = {}
        # conservation ledger (obs/ledger.py): every sink gets a digest
        # account + an emit-edge invariant (in == emitted + filtered),
        # and chained hand-offs count handed/received rows. The wrap
        # happens AFTER the obs wiring above so the RetryingSink
        # isinstance checks saw the raw sink; LedgerSink folds a row
        # only after every retry resolved.
        self._ledger = getattr(metrics.job_obs, "ledger", None)
        self._ledger_handed = 0    # rows appended to the chain hand-off
        self._ledger_received = 0  # rows fed to THIS runner by upstream
        self._ledger_edges: Optional[list] = None
        self._ledger_side: Optional[dict] = None
        if self._ledger is not None:
            led = self._ledger
            edges = []
            for i in range(len(self.sinks)):
                ops, sink = self.sinks[i]
                contents_fn, persistent = _ledger_contents(sink)
                acct = led.register_sink(f"sink{i}", contents_fn, persistent)
                self.sinks[i] = (ops, LedgerSink(sink, acct))
                edges.append(led.emit_edge(acct.name))
            self._ledger_edges = edges
            side = {}
            for tag in list(self.side_sinks):
                ops, sink = self.side_sinks[tag]
                contents_fn, persistent = _ledger_contents(sink)
                acct = led.register_sink(
                    f"side:{tag}", contents_fn, persistent
                )
                self.side_sinks[tag] = (ops, LedgerSink(sink, acct))
                side[tag] = led.emit_edge(acct.name)
            self._ledger_side = side
        # flight breadcrumb: one per program compile (no-op when obs off)
        self._flight.record(
            "program_built",
            operator=self.obs.name or self.program.operator_name,
            key_capacity=cfg.key_capacity,
            shards=self.program.n_shards,
        )

    _COUNTER_NAMES = (
        "window_fires", "late_dropped", "alert_overflow",
        "exchange_overflow", "buffer_overflow", "evicted_unfired",
        "cep_matches", "cep_timeouts",
    )

    def snapshot_counter_baseline(self):
        if not isinstance(self.state, dict):
            return
        present = {
            n: self.state[n] for n in self._COUNTER_NAMES if n in self.state
        }
        if present:
            self._counter_baseline = {
                n: int(v) for n, v in jax.device_get(present).items()
            }

    def refresh_rules(self):
        """Swap the device rule leaves to the RuleSet's CURRENT values
        and version: tiny H2D transfers, never a recompile — the jitted
        step reads rules as runtime data (tpustream/broadcast). On a
        mesh the leaves re-place replicated (P()), so every shard
        applies version N at the same batch boundary.

        One exception: when tenant capacity GREW since the last swap
        (tpustream/tenancy admitted a slot past the current [T]), the
        leaf shapes change and a silent jit retrace would follow with no
        cause attribution. That case routes through
        :meth:`_grow_tenant_capacity` — drained, flight-recorded, and
        cause-tagged like key-capacity growth."""
        ruleset = getattr(self.program, "ruleset", None)
        if (
            ruleset is None
            or not isinstance(self.state, dict)
            or RULES_KEY not in self.state
        ):
            return
        leaves = ruleset.device_leaves()
        old = self.state[RULES_KEY]
        if any(
            tuple(getattr(v, "shape", ())) != tuple(
                getattr(old.get(k), "shape", ())
            )
            for k, v in leaves.items()
        ) or set(leaves) != set(old):
            self._grow_tenant_capacity()
            return
        self._swap_rule_leaves(leaves)

    def _swap_rule_leaves(self, leaves):
        """Place {name: array} rule leaves + the version scalar into
        ``self.state`` (replicated on a mesh)."""
        ruleset = self.program.ruleset
        version = jnp.asarray(ruleset.version, jnp.int64)
        mesh = getattr(self.program, "mesh", None)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(mesh, P())

            def _place(x):
                if self._multiproc:
                    a = np.asarray(x)
                    return jax.make_array_from_callback(
                        a.shape, sharding, lambda idx, a=a: a[idx]
                    )
                return jax.device_put(x, sharding)

            leaves = {k: _place(v) for k, v in leaves.items()}
            version = _place(version)
        state = dict(self.state)
        state[RULES_KEY] = leaves
        state[RULE_VERSION_KEY] = version
        self.state = state

    def _grow_tenant_capacity(self, cause: str = "tenant_capacity_growth"):
        """Re-shape the rule subtree after the RuleSet's tenant capacity
        changed (slot admission past [T] doubles the vectors — the
        tenancy analogue of `_grow_key_capacity`). Only the rule leaves
        change shape, so no state migration is needed; the step is
        rebuilt cause-tagged so the compile registry attributes the
        retrace to tenant growth instead of a silent miss."""
        ruleset = self.program.ruleset
        self.drain_inflight()
        old = self.state[RULES_KEY]
        old_cap = next(
            (
                v.shape[0]
                for v in old.values()
                if getattr(v, "ndim", 0) == 1
            ),
            0,
        )
        self._flight.record(
            "tenant_capacity_grown",
            operator=self.obs.name or self.program.operator_name,
            old_capacity=old_cap,
            new_capacity=ruleset.tenant_capacity,
            cause=cause,
        )
        self._recompile_cause = cause
        self.step = None
        self._empty_cache = None
        self._swap_rule_leaves(ruleset.device_leaves())

    def _check_capacity(self):
        """Keyed state grows without bound, Flink's contract
        (chapter2/README.md:8-10): when the distinct-key count passes
        the current capacity, rebuild the program at 2x and migrate the
        state — amortized one recompile per doubling. Runs before the
        batch whose new keys would overflow ever reaches the device, so
        no record is lost. The intern table is replay-deterministic, so
        multi-host processes take the (collective) growth path at the
        same feed."""
        if self.plan.key_pos is None:
            return
        if self.plan.synthetic_key:
            # the derived-key table lives on the plan, outside the
            # (visible-record) pre chain
            table = self.plan.tables[-1] if self.plan.tables else None
        else:
            table = self.program.pre_chain.out_tables[self.plan.key_pos]
        if table is None:
            return
        if len(table) > self.cfg.key_capacity:
            # one rebuild straight to the needed power-of-two multiple,
            # not one per doubling: a batch can intern many new keys
            cap = self.cfg.key_capacity
            while cap < len(table):
                cap *= 2
            self._grow_key_capacity(cap)

    def _grow_key_capacity(
        self,
        new_capacity: Optional[int] = None,
        cause: str = "key_capacity_growth",
    ):
        """Rebuild the program at ``new_capacity`` (default 2x) and
        migrate device state: key-sharded leaves block-copy into the
        head of each shard's larger region (interned ids are stable and
        the shard count is unchanged, so every key keeps its shard and
        local row); replicated leaves (ring metadata, watermarks,
        counters) carry over as-is."""
        import dataclasses

        from jax.sharding import NamedSharding, PartitionSpec, PartitionSpec as P

        from ..parallel.mesh import AXIS

        # in-flight emissions were computed against the old program and
        # state (host-evaluated fires read self.state) — settle them
        self.drain_inflight()
        new_cap = new_capacity or self.cfg.key_capacity * 2
        self._flight.record(
            "key_capacity_grown",
            operator=self.obs.name or self.program.operator_name,
            old_capacity=self.cfg.key_capacity,
            new_capacity=new_cap,
            cause=cause,
        )
        old_prog = self.program
        # key-sharded leaves fetch LOCAL shards only (the migration is
        # shard-local: every key keeps its shard and local row, so no
        # cross-host traffic is needed); replicated leaves fetch once
        old_leaves = [
            self._fetch_local(l) if self._multiproc else np.asarray(
                jax.device_get(l)
            )
            for l in jax.tree_util.tree_leaves(self.state)
        ]
        self.cfg = dataclasses.replace(self.cfg, key_capacity=new_cap)
        self.program = build_program(self.plan, self.cfg)
        # trace-time flags the chain builder installed on the old
        # program would be silently dropped by the rebuild (KeyError
        # 'ts' / scrambled multi-host hand-off order)
        for flag in ("emit_ts", "emit_chain_key"):
            if getattr(old_prog, flag, False):
                setattr(self.program, flag, True)
        self._inner_step = self.program.jitted_step()
        self._recompile_cause = cause
        self.step = None
        self._empty_cache = None
        target = self.program.init_state()
        t_leaves, treedef = jax.tree_util.tree_flatten(target)
        spec_leaves = jax.tree_util.tree_leaves(
            self.program.state_specs(target),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
        mesh = getattr(self.program, "mesh", None)
        nproc = jax.process_count()
        local_shards = (
            self.program.n_shards // nproc if self._multiproc else None
        )
        migrated = []
        for old, like, spec in zip(old_leaves, t_leaves, spec_leaves):
            key_sharded = len(spec) and spec[0] == AXIS
            if key_sharded:
                init_host = np.asarray(jax.device_get(like))
                if self._multiproc:
                    rows = init_host.shape[0] // nproc
                    pi = jax.process_index()
                    leaf = self.program.grow_key_leaf(
                        old, init_host[pi * rows : (pi + 1) * rows],
                        shards=local_shards,
                    )
                else:
                    leaf = self.program.grow_key_leaf(old, init_host)
            else:
                leaf = old
            if mesh is None:
                migrated.append(leaf)
            elif self._multiproc and key_sharded:
                migrated.append(
                    jax.make_array_from_process_local_data(
                        NamedSharding(mesh, spec), leaf, like.shape
                    )
                )
            elif self._multiproc:
                migrated.append(
                    jax.make_array_from_callback(
                        leaf.shape,
                        NamedSharding(mesh, spec),
                        lambda idx, a=leaf: a[idx],
                    )
                )
            else:
                migrated.append(
                    jax.device_put(leaf, NamedSharding(mesh, spec))
                )
        self.state = jax.tree_util.tree_unflatten(treedef, migrated)
        if self._multiproc:
            # the rebuilt program needs the same multi-host hooks the
            # constructor installed on the original
            self.program._host_fetch = self._fetch_local
            self._data_sharding = NamedSharding(mesh, P(AXIS))

    def _device_inputs(self, batch: Batch, domain: TimeCharacteristic):
        cols = [np.asarray(c.data) for c in batch.columns]
        valid = np.asarray(batch.valid)
        if domain == TimeCharacteristic.EventTime and batch.ts is not None:
            ts = np.asarray(batch.ts)
        else:
            ts = np.asarray(
                batch.proc_ts
                if batch.proc_ts is not None
                else np.zeros(batch.n, dtype=np.int64)
            )
        return self._pack(cols, valid, ts)

    _I32_SPAN = 0x7FFF_FFFF
    _U16_SPAN = 0xFFFF

    def _initial_modes(self):
        """Sticky per-column wire mode chains (narrowest first):
        int64 -> d16 (uint16 delta) -> d32 (int32 delta) -> raw;
        float64 -> f32 (exact-round-trip float32) -> raw;
        interned string ids (int32) -> i16 -> raw;
        bool columns and the valid mask -> bits (8 rows/byte).
        A demoted column stays demoted (at most one recompile each).
        An order-only float64 (``program.image_fields``) goes as its
        exact int64 image (``img``), which never demotes."""
        compress = self.cfg.h2d_compress
        # bit-packing changes the wire leaf's leading dim from [B] to
        # [B/8]; the multi-host gshard split slices rows per process, so
        # those leaves must keep one element per row there
        packed = self.cfg.packed_wire and not self._multiproc
        i64_mode = (
            "d16" if compress and packed else "d32" if compress else "raw"
        )
        modes = []
        images = self.program.image_fields
        for i, k in enumerate(self.in_kinds):
            if i in images:
                modes.append("img")
            elif k == "i64":
                modes.append(i64_mode)
            elif k == "f64" and packed:
                modes.append("f32")
            elif k == STR and packed:
                modes.append("i16")
            elif k == "bool" and packed:
                modes.append("bits")
            else:
                modes.append("raw")
        self._col_modes = tuple(modes)
        self._ts_mode = i64_mode
        self._valid_mode = "bits" if packed else "raw"

    def _pack(self, cols, valid, ts):
        """Numpy-side wire packing per the sticky column modes
        (h2d_compress delta coding + packed_wire narrowing); demotes a
        column down its mode chain — and rebuilds the step once — when
        a batch's valid rows no longer fit the narrow form."""
        if self._col_modes is None:
            self._initial_modes()
        all_valid = bool(valid.all())
        any_valid = all_valid or bool(valid.any())

        def pack_one(arr, mode):
            if mode in ("d32", "d16"):
                if not any_valid:
                    z = np.zeros(
                        arr.shape, np.uint16 if mode == "d16" else np.int32
                    )
                    return z, np.int64(0), mode
                va = arr if all_valid else arr[valid]
                lo = va.min()
                # Python-int span: an int64 subtraction could wrap for
                # full-range columns and silently pass the check
                span = int(va.max()) - int(lo)
                if mode == "d16" and span <= self._U16_SPAN:
                    # invalid/padded rows wrap mod 2^16 — same masked-
                    # garbage contract as d32's wrap, nothing reads them
                    return (arr - lo).astype(np.uint16), np.int64(lo), mode
                if span <= self._I32_SPAN:
                    mode = "d32" if self.cfg.h2d_compress else "raw"
                    if mode == "d32":
                        return (arr - lo).astype(np.int32), np.int64(lo), mode
                return arr, np.int64(0), "raw"
            if mode == "f32":
                f = arr.astype(np.float32)
                back = f.astype(np.float64)
                ok = back == arr  # NaN demotes: conservative, lossless
                if bool(ok.all() if all_valid else ok[valid].all()):
                    return f, np.int64(0), mode
                return arr, np.int64(0), "raw"
            if mode == "i16":
                va = arr if all_valid else arr[valid]
                if not any_valid or (
                    int(va.min()) >= -0x8000 and int(va.max()) <= 0x7FFF
                ):
                    return arr.astype(np.int16), np.int64(0), mode
                return arr, np.int64(0), "raw"
            if mode == "bits":
                # 8 rows/byte; the step unpacks with a shift table and
                # slices back to batch_size (bits is lossless — never
                # demotes)
                return np.packbits(arr.astype(bool)), np.int64(0), mode
            if mode == "img":
                return f64_image(arr), np.int64(0), mode
            return arr, np.int64(0), mode

        packed, bases, modes = [], [], []
        for arr, mode in zip(cols, self._col_modes):
            p, b, m = pack_one(arr, mode)
            packed.append(p)
            bases.append(b)
            modes.append(m)
        ts_p, ts_b, ts_m = pack_one(ts, self._ts_mode)
        if tuple(modes) != self._col_modes or ts_m != self._ts_mode:
            # staged uploads were packed (and will be expanded) under the
            # OLD layout: run them against the old step before it rebuilds
            self._flush_uploads()
            self._col_modes, self._ts_mode = tuple(modes), ts_m
            self._recompile_cause = "batch_shape_change"
            self.step = None  # rebuild for the demoted layout
            self._empty_cache = None
            return self._pack(cols, valid, ts)
        if self._valid_mode == "bits":
            valid_p = np.packbits(valid)
        else:
            valid_p = valid
        n_img = self._col_modes.count("img")
        if n_img and any_valid:
            self._images_packed.inc(
                n_img * (len(valid) if all_valid else int(valid.sum()))
            )
        return tuple(packed), tuple(bases), valid_p, ts_p, ts_b

    def _ensure_step(self):
        if self.step is None:
            self.step = self._counted_step(self._inner_step)

    # -- multi-host helpers ---------------------------------------------
    def _gshard(self, a: np.ndarray):
        """Assemble a globally sharded [B] input from this process's
        contiguous row slice (all processes hold the same full batch;
        each donates its own part — no cross-host data movement)."""
        procs = jax.process_count()
        rows = a.shape[0] // procs
        pi = jax.process_index()
        return jax.make_array_from_process_local_data(
            self._data_sharding, a[pi * rows : (pi + 1) * rows], a.shape
        )

    def _fetch_local(self, tree):
        """device_get that returns only THIS process's shards of
        non-fully-addressable leaves (each process dispatches its own
        shards' emissions). Replicated leaves — scalars like the
        watermark/`hi`, and per-ring metadata — live on every device,
        so one local copy IS the whole value."""
        def get(x):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                shards = list(x.addressable_shards)
                replicated = x.ndim == 0 or all(
                    (sl.start in (None, 0))
                    and (sl.stop in (None, x.shape[d]))
                    for s in shards
                    for d, sl in enumerate(s.index)
                )
                if replicated:
                    return np.asarray(shards[0].data)
                shards.sort(
                    key=lambda s: tuple(sl.start or 0 for sl in s.index)
                )
                return np.concatenate(
                    [np.asarray(s.data) for s in shards]
                )
            return np.asarray(x)

        return jax.tree_util.tree_map(get, tree)

    def _local_row_base(self, local_len: int) -> int:
        """Global row offset of this process's emission slice (for the
        per-shard ``order`` indices rolling/count programs emit)."""
        if not self._multiproc:
            return 0
        local_shards = self.program.n_shards // jax.process_count()
        per_shard = local_len // local_shards
        return jax.process_index() * local_shards * per_shard

    def feed(self, batch: Batch, wm_lower: int, t_batch: Optional[float] = None,
             markers=None, step: Optional[int] = None):
        """Run ``batch`` through the step, one step per ``batch_size``
        rows. ``step`` is the id the source pull drew for the batch; its
        first sub-batch carries it, later ones draw fresh ids."""
        cfg = self.cfg
        if markers:
            self._pending_markers.extend(markers)
        # sampled flight-path probes get the pack hop timed; the span
        # lands once (first sub-batch), on the batch they rode
        traced = None
        if self._pending_markers:
            traced = [
                m for m in self._pending_markers
                if getattr(m, "trace_id", 0)
            ] or None
        self._check_capacity()
        if self._state_mem is not None:
            self._state_mem.observe_batch(batch)
        if t_batch is None:
            t_batch = time.perf_counter()
        for start in range(0, batch.n, cfg.batch_size):
            sub = Batch(
                min(cfg.batch_size, batch.n - start),
                [
                    Column(c.kind, c.data[start : start + cfg.batch_size], c.table)
                    for c in batch.columns
                ],
                ts=None if batch.ts is None else batch.ts[start : start + cfg.batch_size],
                proc_ts=None
                if batch.proc_ts is None
                else batch.proc_ts[start : start + cfg.batch_size],
                valid=batch.valid[start : start + cfg.batch_size],
            )
            padded = sub.pad_to(cfg.batch_size)
            sid = step if start == 0 and step is not None else self._new_step()
            t0p = time.perf_counter() if traced is not None else 0.0
            with self.obs.span("pack", sid):
                inputs = self._device_inputs(
                    padded, self.plan.time_characteristic
                )
            if traced is not None:
                dur = time.perf_counter() - t0p
                for m in traced:
                    m.add_span("pack", t0=t0p, dur=dur, step=sid)
                traced = None
            self._stage_step(inputs, wm_lower, t_batch, sid)
            if self.count_input:
                self.metrics.records_in += int(sub.n)
                self.obs.records_in.inc(int(sub.n))
            # with a max_fires_per_step budget, or after a batch with no
            # valid row (whose step may defer ends that overflow
            # alert_capacity), drain deferred window ends BEFORE the
            # next batch can add records to their windows — each drain
            # step still fires at most `budget` ends, so the per-step
            # latency bound holds while no fire is ever lost
            self._drain(wm_lower, t_batch, empty=not padded.valid.any())

    @staticmethod
    def _wire_nbytes(inputs) -> int:
        """Wire bytes of one packed step input (the h2d_bytes_total
        series): packed columns + valid + ts; the per-column base
        scalars ride along as 8 bytes each."""
        packed, bases, valid, ts_p, _ts_b = inputs
        return (
            sum(int(p.nbytes) for p in packed)
            + int(valid.nbytes)
            + int(ts_p.nbytes)
            + 8 * (len(bases) + 1)
        )

    def _stage_step(self, inputs, wm_lower: int, t_batch=None,
                    step: Optional[int] = None):
        """Run one packed batch through the upload side of the pipeline:
        at h2d_depth 1 (or when staging is disabled) the step runs
        immediately and the transfer rides the dispatch; deeper, the
        batch's device_put is issued NOW (async) and the step runs up to
        _h2d_ahead feeds later — by which point the transfer has crossed
        the wire behind the previous steps' blocking fetches."""
        if self.obs.enabled:
            self._h2d_bytes.inc(self._wire_nbytes(inputs))
        if not self._h2d_ahead:
            self._run_step(inputs, wm_lower, t_batch, step=step)
            return
        packed, bases, valid, ts_p, ts_b = inputs
        traced = (
            [m for m in self._pending_markers if getattr(m, "trace_id", 0)]
            if self._pending_markers else ()
        )
        t0h = time.perf_counter() if traced else 0.0
        with self.obs.span("h2d", step):
            put = (
                jax.device_put
                if self._h2d_sharding is None
                else self._sharded_put
            )
            packed, valid, ts_p = put((packed, valid, ts_p))
        if traced:
            dur = time.perf_counter() - t0h
            for m in traced:
                m.add_span("h2d", t0=t0h, dur=dur)
        # markers detach at stage time so they ride THIS batch's step,
        # not whichever older batch the staging queue pops next
        if self._pending_markers:
            markers = self._pending_markers
            self._pending_markers = []
        else:
            markers = None
        self._upload_q.append(
            ((packed, bases, valid, ts_p, ts_b), wm_lower, t_batch, markers,
             step)
        )
        while len(self._upload_q) > self._h2d_ahead:
            self._pop_upload()

    def _sharded_put(self, tree):
        """device_put for staged batches on a single-process mesh:
        row-shaped leaves place pre-sharded along the batch axis
        (anything the axis doesn't divide falls back to the default
        placement and lets the jit dispatch reshard it)."""
        n = self.program.n_shards

        def put(a):
            if getattr(a, "ndim", 0) >= 1 and a.shape[0] % n == 0:
                return jax.device_put(a, self._h2d_sharding)
            return jax.device_put(a)

        return jax.tree_util.tree_map(put, tree)

    def _pop_upload(self):
        inputs, wm_lower, t_batch, markers, step = self._upload_q.pop(0)
        self._run_step(
            inputs, wm_lower, t_batch,
            markers=() if markers is None else markers, step=step,
        )

    def _flush_uploads(self):
        """Run every staged batch's step (pipeline barrier: checkpoint,
        rule update, key growth, wire-layout demotion, EOS)."""
        while self._upload_q:
            self._pop_upload()

    def flush(self, wm_lower: int, t_batch: Optional[float] = None):
        """Advance time with an empty batch (processing-time tick / EOS).

        Window programs fire at most ``max_fires_per_step`` window ends
        per step (bounding fire-step latency), and an empty step only as
        many as fit alert_capacity; the loop here drains any deferred
        ends until ``state["pending_fires"]`` reaches zero."""
        # staged batches must step before any clock tick: an empty step
        # jumping ahead of a staged data batch would fire its windows
        # from a pre-batch state
        self._flush_uploads()
        if not self.program.fires_on_clock:
            return
        if t_batch is None:
            t_batch = time.perf_counter()
        self._run_step(self._empty_inputs(), wm_lower, t_batch)
        self._drain(wm_lower, t_batch, empty=True)

    def _empty_inputs(self):
        """Packed step inputs of a batch with no valid row (clock ticks,
        end of stream, drain rounds)."""
        if self._empty_cache is None:
            cfg = self.cfg
            cols = [
                np.zeros(
                    (cfg.batch_size,),
                    dtype=np.int32
                    if k == STR
                    else {"f64": np.float64, "i64": np.int64, "bool": np.bool_}[k],
                )
                for k in self.in_kinds
            ]
            valid = np.zeros((cfg.batch_size,), dtype=bool)
            ts = np.zeros((cfg.batch_size,), dtype=np.int64)
            self._empty_cache = self._pack(cols, valid, ts)
        inputs = self._empty_cache
        if self._h2d_ahead and self._h2d_sharding is not None:
            # placed like the staged data batches, or the step would
            # miss the jit cache on the mesh's input types
            packed, bases, valid, ts_p, ts_b = inputs
            packed, valid, ts_p = self._sharded_put((packed, valid, ts_p))
            inputs = (packed, bases, valid, ts_p, ts_b)
        return inputs

    def _counted_step(self, inner):
        """Wrap the program's jitted step to (a) decode the packed wire
        format on device (delta expansion, dtype widening, bit
        unpacking), (b) also return one scalar count per emission
        stream, so the host can skip fetching the batch-sized emission
        buffers of a step that emitted nothing — on a step with no
        alerts the only D2H traffic is these scalars — and (c) gather
        each firing stream's emitted rows into a small [capacity]
        buffer (device-side output compaction), so a firing step
        fetches ~count rows instead of full [B] outputs."""
        col_modes, ts_mode = self._col_modes, self._ts_mode
        valid_mode = self._valid_mode
        n_rows = self.cfg.batch_size
        compact_cap = self._compact_cap
        skip_main_compact = (
            self.program.main_emission_prefix and self.cfg.parallelism <= 1
        )  # single-chip prefix buffers are already compact (sliced fetch)

        def unpack_bits(p):
            bits = (
                p[:, None] >> jnp.arange(7, -1, -1, dtype=jnp.uint8)
            ) & jnp.uint8(1)
            return bits.reshape(-1)[:n_rows].astype(jnp.bool_)

        def expand(p, b, mode):
            if mode in ("d32", "d16"):
                return p.astype(jnp.int64) + b
            if mode == "f32":
                return p.astype(jnp.float64)
            if mode == "i16":
                return p.astype(jnp.int32)
            if mode == "bits":
                return unpack_bits(p)
            return p

        def compact_stream(stream):
            """Gather one stream's emitted rows (emission order) into
            [compact_cap] buffers: row indices + every [B]-shaped leaf,
            pre-gathered so the host fetch is count-sized. Rows past the
            capacity are simply absent — the host spills to the full
            fetch when count > capacity (exact at any density)."""
            from ..ops import panes as pane_ops

            mask = stream["mask"]
            order = stream.get("order")
            nb = mask.shape[0]
            if order is not None:
                # rolling/count programs emit in device-internal order
                # with a permutation leaf; emission order is ascending j
                # where mask[order[j]] — gather through it so the
                # compact rows land dispatch-ready
                perm_valid = mask[order]
                pos, _cnt = pane_ops.compact_positions(
                    perm_valid, compact_cap
                )
                sel = order[pos]
            else:
                sel, _cnt = pane_ops.compact_positions(mask, compact_cap)

            def gather(a):
                if getattr(a, "ndim", 0) >= 1 and a.shape[0] == nb:
                    return a[sel]
                return a

            comp = {
                k: jax.tree_util.tree_map(gather, v)
                for k, v in stream.items()
                if k not in ("mask", "order")
            }
            comp["__sel__"] = sel.astype(jnp.int32)
            return comp

        def step(state, packed, bases, valid, ts_p, ts_b, wm_lower):
            # named scopes label the step's phases in the compiled
            # program's op metadata (obs/compilation.py op_scopes)
            with jax.named_scope("unpack"):
                cols = tuple(
                    expand(p, b, m)
                    for p, b, m in zip(packed, bases, col_modes)
                )
                if valid_mode == "bits":
                    valid = unpack_bits(valid)
                ts = expand(ts_p, ts_b, ts_mode)
            state, em = inner(state, cols, valid, ts, wm_lower)
            counts = {}
            for name, stream in em.items():
                if "mask" in stream:
                    counts[name] = stream["mask"].sum(dtype=jnp.int32)
                elif "fire" in stream:
                    counts[name] = stream["fire"].sum(dtype=jnp.int32)
            compact = {}
            if compact_cap:
                for name, stream in em.items():
                    if "mask" not in stream:
                        continue
                    if name == "main" and skip_main_compact:
                        continue
                    with jax.named_scope("compact"):
                        compact[name] = compact_stream(stream)
            return state, em, counts, compact

        if self._compile_obs is not None:
            cause = self._recompile_cause
            # any later miss inside this step object is shape-driven
            self._recompile_cause = "batch_shape_change"
            return self._compile_obs.instrument(
                step, cause=cause, donate_argnums=0
            )
        return jax.jit(step, donate_argnums=0)

    def _run_step(self, inputs, wm_lower: int, t_batch=None, markers=None,
                  step: Optional[int] = None):
        """One jitted step + emission dispatch (the only step call site).

        ``markers`` is the staged-upload path handing over the markers it
        detached at stage time; None means take the pending ones here.
        ``step`` is the id of a fed batch; a step with no batch (clock
        tick, deferred fires, end of stream) draws a fresh one."""
        self._ensure_step()
        if self._fault is not None:
            self._fault("device_step")
            if self.program.operator_name == "cep":
                self._fault("cep_step")
            if self.program.n_shards > 1:
                self._fault("exchange")
        packed, bases, valid, ts_p, ts_b = inputs
        if self._multiproc:
            # batch-sized leaves become global arrays (scalars replicate
            # as plain numpy — identical on every process by replay
            # determinism)
            packed = tuple(self._gshard(p) for p in packed)
            valid = self._gshard(valid)
            ts_p = self._gshard(ts_p)
        sid = step if step is not None else self._new_step()
        self._flight.set_active(self.obs.name or self.program.operator_name)
        if self._inflight and self._slices_fetch:
            self._prepare_ahead()
        with self.obs.span("dispatch", sid):
            with Stopwatch() as sw:
                self.state, emissions, counts, compact = self.step(
                    self.state, packed, bases, valid, ts_p, ts_b,
                    jnp.asarray(wm_lower, jnp.int64),
                )
                for leaf in counts.values():
                    leaf.copy_to_host_async()
        self._dispatched += 1
        self.metrics.step_times_s.append(sw.elapsed)
        self.obs.steps.inc()
        self.obs.dispatch_time_s.observe(sw.elapsed)
        # markers ride this step's inflight entry: their source->edge
        # latency is recorded when the entry's emissions dispatch, so
        # pipelining delay (async_depth) is measured, not hidden
        # detach, never alias: an empty ``_pending_markers`` must not ride
        # the entry as a live reference, or markers accepted while this
        # step is in flight would appear in it retroactively AND drain
        # into a later step — recording twice
        if markers is not None:
            step_markers = markers
        elif self._pending_markers:
            step_markers = self._pending_markers
            self._pending_markers = []
        else:
            step_markers = ()
        for m in step_markers:
            if getattr(m, "trace_id", 0):
                m.add_span(
                    "device_step", t0=sw.t0, dur=sw.elapsed,
                    step=sid,
                    operator=self.obs.name or self.program.operator_name,
                )
        self._inflight.append(_Inflight(
            emissions, counts, compact, t_batch, step_markers, sid,
            self._dispatched,
        ))
        self.obs.inflight.set(len(self._inflight))
        while len(self._inflight) > self._max_inflight:
            self._retire(self._inflight[0])
            del self._inflight[0]

    @property
    def _slices_fetch(self) -> bool:
        """Whether a fetch launches device slice programs: on one chip,
        the prefix-compacted 'main' stream and device compaction. Only
        there is a step's fetch planned before the next step's dispatch
        (_run_step); multi-host and sharded fetches read whole buffers
        and keep their order."""
        return (
            not self._multiproc
            and self.cfg.parallelism <= 1
            and bool(self._compact_cap or self.program.main_emission_prefix)
        )

    def drain_inflight(self):
        """Dispatch every pending step's emissions (checkpoint barrier /
        end of stream). Staged uploads step first — their batches are
        consumed-but-unstepped and a barrier must settle them too."""
        self._flush_uploads()
        entries, self._inflight = self._inflight, []
        for e in entries:
            self._retire(e)

    # -- latency markers (obs/latency.py) ----------------------------------

    def accept_markers(self, markers) -> None:
        """Markers arriving at this stage (from the source stamper or the
        upstream stage); they ride the next step's inflight entry."""
        if markers:
            self._pending_markers.extend(markers)

    def _record_markers(self, markers) -> None:
        """A dispatched step's markers have now crossed this operator
        edge: record source->here age, then route them onward — to the
        downstream stage, or (terminal stage) across every sink edge."""
        now_ns = time.monotonic_ns()
        edge = self.obs.name or self.program.operator_name
        for m in markers:
            self._e2e_hist.observe(m.observe(edge, now_ns))
        if self.downstream is not None:
            self._marker_out.extend(markers)
            return
        for m in markers:
            if m.tenant is None:
                continue
            h = self._tenant_e2e.get(m.tenant)
            if h is None:
                h = self.metrics.job_obs.group.group(
                    tenant=m.tenant
                ).histogram("tenant_e2e_latency_ms")
                self._tenant_e2e[m.tenant] = h
            h.observe(m.age_ms(now_ns))
        for i, h in enumerate(self._sink_e2e):
            for m in markers:
                h.observe(m.observe(f"sink{i}", now_ns))
        # sampled flight-path probes are complete at the terminal stage:
        # their span trees land in the job's record-trace log (the
        # /trace.json + dump --trace lineage track)
        log = self.metrics.job_obs.traces
        for m in markers:
            if getattr(m, "trace_id", 0):
                log.add(m)

    def settle_markers(self) -> None:
        """End of stream: no further steps will run, so record any
        marker still waiting for one (guarantees markers are never lost
        — the e2e series always reflects every stamped marker), then
        cascade down the chain."""
        if self._pending_markers:
            ms, self._pending_markers = self._pending_markers, []
            self._record_markers(ms)
        if self.downstream is not None:
            if self._marker_out:
                self.downstream.accept_markers(self._marker_out)
                self._marker_out = []
            self.downstream.settle_markers()

    def chain_to(self, downstream: "Runner"):
        self.downstream = downstream
        downstream.count_input = False
        if self._ledger is not None:
            # conservation on the hand-off: rows this runner handed ==
            # rows the downstream received + rows still parked in the
            # hand-off buffers (closures — the evaluator reads live)
            self._ledger.register_chain_edge(
                "chain:"
                + (downstream.obs.name or downstream.program.operator_name),
                lambda u=self, d=downstream: (
                    u._ledger_handed,
                    d._ledger_received,
                    u._ledger_buffered(),
                ),
            )

    def chain(self) -> List["Runner"]:
        out, r = [], self
        while r is not None:
            out.append(r)
            r = r.downstream
        return out

    def _ledger_buffered(self) -> int:
        """Rows handed to the chain but not yet pumped downstream: the
        buffered term of the chain conservation edge. Single-host entry
        shapes only — the ledger is forced off under multi-host."""
        n = len(self._chain_rows)
        for entry in self._chain_buf:
            if entry and not isinstance(entry[0], str):
                cols = entry[0]
                n += len(cols[0]) if cols else 0
        return n

    @staticmethod
    def _downstream_is_event_time(d: "Runner") -> bool:
        return (
            getattr(d.program, "domain", None) == TimeCharacteristic.EventTime
        )

    def _build_lazy_downstream(self) -> "Runner":
        """Process()-fed chains resolve the downstream record schema from
        the buffered collected rows (the user function may emit any
        shape), then build the remaining runner chain. Kinds WIDEN
        across all buffered rows — a median fn emits ints on odd counts
        and floats on even ones, and first-row inference would silently
        truncate the floats."""
        from ..records import StringTable

        kinds = _infer_row_kinds([item for item, _, _ in self._chain_rows])
        p2 = self._lazy_plans[0]
        p2.record_kinds.extend(kinds)
        p2.tables.extend(StringTable() if k == STR else None for k in kinds)
        if p2.synthetic_key:
            p2.record_kinds.append(STR)
            p2.tables.append(DerivedKeyTable())
        d = _make_runner_chain(self._lazy_plans, self.cfg, self.metrics)
        # the inferred schema is snapshotted with checkpoints so a
        # restored run can rebuild this runner without re-inference
        d._lazy_schema = True
        self._lazy_plans = []
        self.chain_to(d)
        _wire_chain_ts(self, d)
        return d

    def _rows_to_cols(self):
        """Convert buffered process() rows to the downstream's columnar
        schema (established at lazy build; values coerce to the widened
        plan kinds)."""
        rows = [item for item, _, _ in self._chain_rows]
        ts = (
            np.asarray([t for _, t, _ in self._chain_rows], dtype=np.int64)
            if self._chain_ts
            else None
        )
        d = self.downstream
        kinds, tables = d.plan.record_kinds, d.plan.tables
        if d.plan.synthetic_key:
            # visible columns only; pump_chain appends the derived key
            kinds, tables = kinds[:-1], tables[:-1]
        fields = [_row_fields(r) for r in rows]

        def _bad(i, what, kind, hint=""):
            # the schema froze at the first pump; a later emission of a
            # different type would otherwise coerce silently (int ->
            # True, float -> truncated int) or die in an opaque numpy
            # TypeError (str under np.floor)
            raise ValueError(
                f"chained process() stage emitted a {what} value in "
                f"field {i} after its schema was inferred as {kind} "
                f"from earlier rows; emit one consistent type{hint}"
            )

        cols = []
        for i, (k, table) in enumerate(zip(kinds, tables)):
            vs = [f[i] for f in fields]
            if k == STR:
                cols.append(table.intern_many([str(v) for v in vs]))
                continue
            if k == "bool":
                if not all(isinstance(v, (bool, np.bool_)) for v in vs):
                    _bad(i, "non-bool", "bool")
                cols.append(np.asarray(vs, dtype=np.bool_))
                continue
            if any(isinstance(v, (bool, np.bool_)) for v in vs):
                # np.asarray would fold True into 1/1.0 with no error —
                # the same silent-coercion class the bool branch rejects
                _bad(i, "bool", "int" if k == "i64" else "float")
            arr = np.asarray(vs)
            if arr.dtype.kind not in "iuf":
                _bad(i, "non-numeric", "int" if k == "i64" else "float")
            if k == "i64":
                if arr.dtype.kind == "f" and not np.all(
                    arr == np.floor(arr)
                ):
                    _bad(i, "fractional", "int",
                         " (e.g. always float)")
                cols.append(arr.astype(np.int64))
            else:
                cols.append(arr.astype(np.float64))
        self._chain_rows = []
        return cols, ts, kinds, tables

    def _gather_chain_rows(self):
        """Multi-host process()-fed chain hand-off: allgather every
        process's locally-evaluated fire rows (pickled — rows are user
        objects) and merge them in the single-process evaluation order
        (each row carries its evaluation-loop position). After this,
        every process holds the IDENTICAL global row list, so schema
        inference and the downstream SPMD feed agree everywhere.

        Called once per pump on every process (the pump cadence is
        driven by source batches, which replay identically), keeping the
        collective call count aligned even when only one side fired."""
        import pickle

        from jax.experimental import multihost_utils as mh

        # most pumps fire nothing anywhere: settle that with one scalar
        # gather (SPMD-identical result, so every process skips the blob
        # gather together — collective counts stay aligned)
        n_rows = mh.process_allgather(
            np.asarray([len(self._chain_rows)], np.int64)
        ).reshape(-1)
        if not int(n_rows.sum()):
            return
        blob = np.frombuffer(
            pickle.dumps(self._chain_rows), dtype=np.uint8
        )
        counts = mh.process_allgather(
            np.asarray([blob.shape[0]], np.int64)
        ).reshape(-1)
        mx = int(counts.max())
        pad = np.zeros(mx - blob.shape[0], np.uint8)
        g = mh.process_allgather(np.concatenate([blob, pad]))
        merged = []
        for p in range(g.shape[0]):
            merged.extend(pickle.loads(g[p, : int(counts[p])].tobytes()))
        merged.sort(key=lambda e: e[2])
        self._chain_rows = merged

    def pump_chain(self, proc_now: int):
        """Move buffered emissions to the downstream runner (or tick its
        processing-time clock when there are none), then cascade."""
        d = self.downstream
        if (
            self._multiproc
            and getattr(self.program, "host_evaluated", False)
            and (d is not None or self._lazy_plans)
        ):
            self._gather_chain_rows()
        if d is None and self._chain_rows and self._lazy_plans:
            d = self._build_lazy_downstream()
        if d is None:
            return
        if self._marker_out:
            # markers recorded at this edge continue downstream with the
            # same pump that moves the data they travelled with
            d.accept_markers(self._marker_out)
            self._marker_out = []
        fed = False
        if self._chain_rows:
            cols, ts, kinds, tables = self._rows_to_cols()
        elif self._chain_buf and self._multiproc:
            # multi-host chain hand-off: every process must feed the
            # IDENTICAL global batch to its (SPMD) downstream stage, so
            # each step's local rows allgather across processes and then
            # take the canonical order — (end, key) for window stages
            # (= the single-chip fire order), the global post-exchange
            # row index for rolling/count stages (= the single-process
            # emission order). One gather round per buffered step keeps
            # the collective call count aligned across processes.
            bufs, self._chain_buf = self._chain_buf, []
            parts_cols: List[list] = []
            parts_ts: List[np.ndarray] = []
            for entry in bufs:
                if entry[0] == "win":
                    _, ecols, eend, ekey = entry
                    g = _allgather_rows(list(ecols) + [eend, ekey])
                    gend, gkey = g[-2], g[-1]
                    if not len(gend):
                        continue
                    o = np.lexsort((gkey, gend))
                    parts_cols.append([c[o] for c in g[:-2]])
                    parts_ts.append(gend[o] - 1)
                else:  # "arr"
                    _, ecols, gorder, ets = entry
                    nc = len(ecols)
                    extra = [gorder] + ([ets] if ets is not None else [])
                    g = _allgather_rows(list(ecols) + extra)
                    go = g[nc]
                    if not len(go):
                        continue
                    o = np.argsort(go, kind="stable")
                    parts_cols.append([c[o] for c in g[:nc]])
                    if ets is not None:
                        parts_ts.append(g[-1][o])
            if parts_cols:
                cols = [
                    np.concatenate([p[i] for p in parts_cols])
                    for i in range(len(parts_cols[0]))
                ]
                ts = np.concatenate(parts_ts) if self._chain_ts else None
            else:
                cols = []
                ts = None
            kinds, tables = self.program.out_kinds, self.program.out_tables
        elif self._chain_buf:
            bufs, self._chain_buf = self._chain_buf, []
            cols = [
                np.concatenate([b[0][i] for b in bufs])
                for i in range(len(bufs[0][0]))
            ]
            ts = (
                np.concatenate([b[1] for b in bufs])
                if self._chain_ts
                else None
            )
            kinds, tables = self.program.out_kinds, self.program.out_tables
        else:
            cols = []
        if cols and len(cols[0]):
            n = len(cols[0])
            if d.plan.synthetic_key:
                # computed KeySelector on the downstream stage: derive
                # the key from the (identical-on-every-process) batch
                cols = list(cols) + [derive_key_column(d.plan, cols, n)]
                kinds = list(kinds) + [STR]
                tables = list(tables) + [d.plan.tables[-1]]
            columns = [
                Column(k, c, t) for k, c, t in zip(kinds, cols, tables)
            ]
            batch = Batch(
                n, columns, ts=ts,
                proc_ts=np.full(n, proc_now, dtype=np.int64),
            )
            # event-time stages let the data drive the watermark; the
            # processing clock floor belongs to processing-time stages
            wl = (
                LONG_MIN + 1
                if self._downstream_is_event_time(d)
                else proc_now - 1
            )
            d.feed(batch, wl)
            if self._ledger is not None:
                # downstream side of the chain conservation edge:
                # counted here (upstream pump) so feed() itself stays
                # ledger-agnostic for source-fed runners
                d._ledger_received += n
            d._last_tick = proc_now
            fed = True
        if (
            not fed
            and getattr(d, "_last_tick", None) != proc_now
            and not self._downstream_is_event_time(d)
        ):
            # clock tick, at most once per distinct proc_now: an empty
            # flush step per source batch would double device launches
            # (event-time stages fire from data/EOS, never the clock)
            d.flush(proc_now - 1)
            d._last_tick = proc_now
        d.pump_chain(proc_now)

    def drain_chain(self, proc_now: int):
        """Flush every stage's in-flight emissions down the chain (the
        checkpoint barrier): after this, all emissions of consumed source
        batches have either reached the sinks or are folded into some
        stage's device state."""
        r = self
        while r is not None:
            r.drain_inflight()
            r.pump_chain(proc_now)
            r = r.downstream

    def _plan_fetch(self, emissions, compact, cnts, seq: int) -> dict:
        """The emission streams worth fetching for one step, given its
        host-side count scalars: skip empty streams, slice prefix-
        compacted buffers to ~count rows, and swap in the device-
        compacted form (count-sized, pre-gathered) when the count fits
        its capacity — past it, spill to the classic full fetch so
        semantics hold at any alert density. ``seq`` is the step's
        place in the dispatch order (_launch_slices)."""
        fetch = {}
        tt = getattr(self.program, "timeout_tag", None)
        for name, stream in emissions.items():
            c = cnts.get(name, 1)
            if not c or (name == "late" and not self.side_sinks):
                continue
            if name == "timeout" and (
                tt is None or tt.id not in self.side_sinks
            ):
                # within()-expired partials are counted on device
                # (cep_timeouts) even when no side output consumes them
                continue
            if (
                name == "main"
                and self.program.main_emission_prefix
                and self.cfg.parallelism <= 1
                # sharded emissions stack one prefix PER SHARD —
                # the global buffer has no single count-row prefix
            ):
                # valid rows are a compacted prefix: fetch the next
                # power-of-two past the count, not the whole
                # alert_capacity buffer (bucketing keeps the number
                # of device slice programs bounded), and at least the
                # recent firing scale: each new size compiles a slice
                # program, so sizes stay few as counts wander
                cap = int(stream["mask"].shape[0])
                b = min(cap, max(
                    1 << max(4, (int(c) - 1).bit_length()),
                    self._prefix_hint,
                ))
                stream = self._launch_slices(stream, b, cap, seq)
            elif name in compact:
                if int(c) <= self._compact_cap:
                    # count-sized fetch: slice the [capacity] compact
                    # buffers to the pow2 bucket past the count (same
                    # bucketing as the prefix path bounds the number of
                    # device slice programs)
                    b = min(
                        self._compact_cap,
                        1 << max(4, (int(c) - 1).bit_length()),
                    )
                    comp = self._launch_slices(
                        compact[name], b, self._compact_cap, seq
                    )
                    comp["__n__"] = int(c)
                    fetch[name] = comp
                    continue
                # spill: denser than the compact buffer — fall through
                # to the exact full fetch, leave a breadcrumb (first
                # spill per stream) and count every occurrence
                self._spill_counter.inc()
                if name not in self._spilled_streams:
                    self._spilled_streams.add(name)
                    self._flight.record(
                        "compaction_spill",
                        operator=self.obs.name or self.program.operator_name,
                        stream=name,
                        count=int(c),
                        capacity=self._compact_cap,
                    )
            fetch[name] = stream
        return fetch

    def _launch_slices(self, stream, b: int, cap: int, seq: int):
        """``_slice_stream`` for the fetch of the ``seq``-th dispatched
        step, counted in ``fetch_slices_total`` and, where a later step
        was already dispatched, ``fetch_slices_behind_step_total``."""
        if self.obs.enabled:
            n = sum(
                1
                for a in jax.tree_util.tree_leaves(stream)
                if self._capacity_leaf(a, cap)
            )
            self._slices_counter.inc(n)
            if self._dispatched > seq:
                self._slices_behind.inc(n)
        return self._slice_stream(stream, b, cap)

    @staticmethod
    def _capacity_leaf(a, cap: int) -> bool:
        """A [cap]-rowed leaf of an emission stream (what a slice cuts)."""
        return getattr(a, "ndim", 0) >= 1 and a.shape[0] == cap

    @staticmethod
    def _slice_stream(stream, b: int, cap: int):
        return jax.tree_util.tree_map(
            lambda a: a[:b] if Runner._capacity_leaf(a, cap) else a,
            stream,
        )

    def _spec_eligible(self, entry: _Inflight) -> bool:
        """Prefix-hint eligibility: a step of a single-chip
        prefix-compacted program with a 'main' stream. One predicate
        for both the hint recorder and the speculative fetch — they must
        agree or hints are recorded for steps that can never use them."""
        return (
            not self._multiproc
            and self.program.main_emission_prefix
            and self.cfg.parallelism <= 1
            and entry.emissions.get("main") is not None
        )

    def _speculative_main(self, entries):
        """For a lone step planned with no later step dispatched (the
        synchronous path, the end-of-stream drain) on prefix-compacted
        programs: a slice of the 'main' stream sized by the PREVIOUS
        firing step's count, fetched in the same round trip as the count
        scalars. When the hint covers the actual count, a firing step
        costs ONE host-device round trip instead of two; the
        speculative bytes are bounded by the hint. Returns
        (stream_slice, hint_rows) or (None, 0)."""
        if (
            len(entries) != 1
            or not self._prefix_hint
            or not self._spec_eligible(entries[0])
        ):
            return None, 0
        e = entries[0]
        main = e.emissions["main"]
        cap = int(main["mask"].shape[0])
        b = min(cap, self._prefix_hint)
        return self._launch_slices(main, b, cap, e.seq), b

    def _plan_entries(self, entries, speculate: bool):
        """Read the entries' count scalars (copied to the host at their
        dispatch) in one device_get and plan each one's fetch, launching
        its slices. With ``speculate`` a lone prefix step also fetches a
        hint-sized 'main' slice in that round trip; its rows then need
        no exact slice."""
        spec, spec_rows = (
            self._speculative_main(entries) if speculate else (None, 0)
        )
        if spec is not None:
            cnts0, spec_fetched = jax.device_get([entries[0].counts, spec])
            cnts_list = [cnts0]
        else:
            cnts_list = jax.device_get([e.counts for e in entries])
        for e, cnts in zip(entries, cnts_list):
            c = int(cnts.get("main", 0))
            if spec is not None and c and c <= spec_rows:
                e.fetched["main"] = spec_fetched
                e.fetch = self._plan_fetch(
                    e.emissions, e.compact,
                    {**cnts, "main": 0},  # rows already on the host
                    e.seq,
                )
            else:
                e.fetch = self._plan_fetch(
                    e.emissions, e.compact, cnts, e.seq
                )
            if c and self._spec_eligible(e):
                # track the recent firing scale (pow2 bucket, one level
                # of headroom) so the next slice or speculation fits it
                self._prefix_hint = min(
                    int(e.emissions["main"]["mask"].shape[0]),
                    1 << max(5, (c - 1).bit_length() + 1),
                )

    def _prepare_ahead(self):
        """Plan, before the next dispatch, the fetch of the oldest step
        in flight when this dispatch retires it, and of each other one
        that has finished on the device. The chip runs programs in launch
        order, so a slice launched after the dispatch would wait for
        that whole step. A step still running that this dispatch does
        not retire (a deep pipeline the device, not the host, paces) is
        planned when it is retired, its slices then behind the later
        steps (``fetch_slices_behind_step_total``)."""
        retires = len(self._inflight) >= self._max_inflight
        todo = [
            e
            for i, e in enumerate(self._inflight)
            if e.fetch is None and ((i == 0 and retires) or e.finished())
        ]
        if todo:
            self._prepare_fetch(todo)

    def _prepare_fetch(self, entries):
        """First half of the fetch of steps still in flight, run just
        before the next step's dispatch: plan them with their exact
        counts, launch their slices (ahead of that step on the chip)
        and start the slices' copies to the host. The _retire of each
        entry then only collects rows already computed. Timed as its own
        ``fetch`` span."""
        with self.obs.span("fetch", entries[-1].step), Stopwatch() as sw:
            self._plan_entries(entries, speculate=False)
            for e in entries:
                for leaf in jax.tree_util.tree_leaves(e.fetch):
                    if isinstance(leaf, jax.Array):
                        leaf.copy_to_host_async()
        for e in entries:
            e.wait_s += sw.elapsed / len(entries)
            for m in e.markers:
                if getattr(m, "trace_id", 0):
                    m.add_span("fetch", t0=sw.t0, dur=sw.elapsed,
                               group=len(entries))

    def _retire(self, entry: _Inflight):
        """Fetch one step's emissions and hand them to the sinks. The
        blocking wait on the device lives here and in _prepare_fetch,
        not in _run_step (dispatch is async): the ``fetch`` span times
        it. step_times_s gets both halves' waits, beside the dispatch
        walls _run_step appends, so it is not the device's time. An
        entry not yet planned (no later step was dispatched: the
        synchronous path, a drain) reads its count scalars here, then
        its still-needed emission streams in a second device_get."""
        with self.obs.span("fetch", entry.step), Stopwatch() as sw:
            if entry.fetch is None:
                self._plan_entries([entry], speculate=True)
            if not entry.fetch:
                fetched = {}
            elif self._multiproc:
                fetched = self._fetch_local(entry.fetch)
            else:
                fetched = jax.device_get(entry.fetch)
        if self.obs.enabled:
            self._account_fetch(entry, fetched)
        wait = sw.elapsed + entry.wait_s
        self.metrics.step_times_s.append(wait)
        self.obs.step_time_s.observe(wait)
        fetched.update(entry.fetched)
        for m in entry.markers:
            if getattr(m, "trace_id", 0):
                m.add_span("fetch", t0=sw.t0, dur=sw.elapsed)
        self._dispatch(fetched, entry.t_batch, entry.step)
        if entry.markers:
            self._record_markers(entry.markers)

    def _account_fetch(self, entry: _Inflight, fetched: dict):
        """fetch_bytes_total / compaction_ratio bookkeeping (obs-enabled
        runs only): actually-fetched bytes vs what the same streams
        would have cost as full [B] buffers. Ratio < 1 means the
        compaction/prefix slicing is cutting D2H wire bytes."""

        def nbytes(tree):
            return sum(
                int(a.nbytes)
                for a in jax.tree_util.tree_leaves(tree)
                if hasattr(a, "nbytes")
            )

        # the count scalars fetch every step regardless
        fetched_b = nbytes(fetched) + 4 * len(entry.counts)
        self._fetch_bytes.inc(fetched_b)
        full_b = sum(
            nbytes(entry.emissions.get(name)) for name in entry.fetch
        )
        if full_b:
            self._compaction_gauge.set(fetched_b / full_b)

    def finalize_metrics(self):
        """Fold the device-side cumulative counters into Metrics (one
        scalar fetch per job, never on the per-batch hot path)."""
        if not isinstance(self.state, dict):
            return
        present = {
            n: self.state[n] for n in self._COUNTER_NAMES if n in self.state
        }
        if present:
            vals = jax.device_get(present)
            for n, val in vals.items():
                # window_fires for the host-evaluated process path is
                # counted host-side; device programs count on device —
                # += merges both
                delta = int(val) - self._counter_baseline.get(n, 0)
                setattr(self.metrics, n, getattr(self.metrics, n) + delta)
                if delta:
                    self.obs.counter(n).inc(delta)
        if self.obs.enabled:
            self._finalize_obs_gauges()

    def _finalize_obs_gauges(self):
        """Expose the device-authoritative scalar clocks as gauges: the
        event-time watermark, newest seen timestamp, and deferred-fire
        backlog. One extra device_get per job, obs-enabled runs only."""
        scalars = self.program.obs_state_scalars(self.state)
        if not scalars:
            return
        vals = jax.device_get(scalars)
        for n, v in vals.items():
            self.obs.gauge("state_" + n).set(int(v))
        wm, max_ts = vals.get("wm"), vals.get("max_ts")
        if wm is not None and max_ts is not None and int(wm) > LONG_MIN:
            # 0 after the end-of-stream MAX watermark; the live lag
            # signal during a run is the job-scope host gauge fed from
            # the timestamp assigner (execute_job)
            self.obs.gauge("watermark_lag").set(max(0, int(max_ts) - int(wm)))

    def check_strict(self):
        """strict_overflow: fail loudly if any lossy counter is nonzero
        (Flink's shuffle/state never silently drops records). Reads the
        counters finalize_metrics() already folded — call it first."""
        if not self.cfg.strict_overflow:
            return
        bad = {n: v for n, v in self.metrics.overflow_counts().items() if v}
        if bad:
            raise RuntimeError(
                "strict_overflow: records were lost or truncated: "
                + ", ".join(f"{n}={v}" for n, v in sorted(bad.items()))
                + " — raise the relevant capacity "
                "(alert_capacity / exchange_capacity_factor / "
                "process_buffer_capacity / pane_ring_slack)"
            )

    def _drain(self, wm_lower: int, t_batch=None, empty: bool = False):
        """Run empty-batch steps until no window fires remain deferred.

        Ends are deferred by the max_fires_per_step budget, or, on a
        step fed no valid row (``empty``), when firing them would
        overflow alert_capacity. Otherwise every step fires all due
        ends, so pending is provably zero: skip even the scalar
        device_get on the hot loop."""
        if self.cfg.max_fires_per_step is None and not empty:
            return
        # a staged empty batch has not stepped yet
        self._flush_uploads()
        pending = (
            self.state.get("pending_fires")
            if isinstance(self.state, dict)
            else None
        )
        if pending is None or int(jax.device_get(pending)) == 0:
            return
        # each round fires at least one end: the first pending end always
        # fits an empty alert buffer (or fires alone, counting overflow)
        max_rounds = self.program.ring.n_fire_candidates + 1
        for _ in range(max_rounds):
            self._run_step(self._empty_inputs(), wm_lower, t_batch)
            if int(jax.device_get(self.state["pending_fires"])) == 0:
                break

    def _chain_cols(self, cols):
        """A step's fetched emission columns as the next stage takes
        them: image fields back to float64 (the sinks' formatter does
        the same for rows)."""
        for i in self.program.image_fields:
            cols[i] = f64_from_image(cols[i])
        return cols

    def _emit_row(self, row, subtask, ts=None, order=None):
        """Fan one emitted record out to every branch: apply the
        branch's host-side map/filter tail, then its sink. Chained
        process() stages buffer the row (with its window timestamp and
        — for the multi-host cross-process merge — the evaluation-loop
        order key the program supplied) for the downstream runner."""
        if self.downstream is not None or self._lazy_plans:
            o = (
                None
                if order is None
                else (self._dispatch_seq,) + tuple(order)
            )
            self._chain_rows.append((row, ts, o))
            self._ledger_handed += 1
            return
        if self._ledger_edges is None:
            for ops, sink in self.sinks:
                item, keep = _apply_ops(ops, row)
                if keep:
                    sink.emit(item, subtask=subtask)
            return
        # ledger on: account the per-branch fan-out (in == emitted +
        # filtered). "in" counts after the emit resolved, so a fatally
        # raising sink (the attempt is abandoned and replayed) does not
        # latch a false violation — real row loss shows up on the
        # contents/digest edges, which survive into the next attempt.
        for (ops, sink), edge in zip(self.sinks, self._ledger_edges):
            item, keep = _apply_ops(ops, row)
            if keep:
                sink.emit(item, subtask=subtask)
            else:
                edge["filtered"] += 1
            edge["in"] += 1

    def _stream_rows(self, stream):
        """Resolve one fetched emission stream to its emitted rows:
        returns ``(sel, take, j_valid)`` where ``sel`` is the row
        indices in emission order, ``take(leaf)`` gathers any
        [B]-shaped leaf to those rows, and ``j_valid`` is the
        emission-order positions (order-carrying streams only; the
        multi-host merge key). Device-compacted streams (``__n__``)
        arrive pre-gathered, so ``take`` is just a count slice; full
        streams gather through the mask (un-permuting via the
        ``order`` leaf when the program emits one)."""
        n = stream.get("__n__")
        if n is not None:
            n = int(n)
            sel = np.asarray(stream["__sel__"])[:n]

            def take(a):
                return np.asarray(a)[:n]

            return sel, take, None
        mask = np.asarray(stream["mask"])
        order = stream.get("order")
        if order is not None:
            # device emitted rows in its internal (sorted) order;
            # order[j] is post-exchange row j's position — un-permute
            # HERE, off the device critical path (numpy gather).
            # Order values address the GLOBAL stacked buffer; under
            # multi-host each process fetched only its slice.
            order = np.asarray(order) - self._local_row_base(mask.shape[0])
            j_valid = np.nonzero(mask[order])[0]
            sel = order[j_valid]
        else:
            j_valid = None
            sel = np.nonzero(mask)[0]

        def take(a):
            return np.asarray(a)[sel]

        return sel, take, j_valid

    def _dispatch(self, emissions, t_batch=None, step: int = -1):
        with self.obs.span("emit", step) as sp:
            rows = self._dispatch_inner(emissions, t_batch, step)
            if self.obs.enabled:
                sp.note(rows=rows)

    def _hand_off(self, branches, columns, subtasks=None):
        """Hand one step's rows (``columns``, converted) to each
        ``(ops, sink)`` branch in turn, in one ``emit_many`` call per
        branch; a branch's map/filter tail runs per row first.
        ``subtasks`` (numpy, one per row) converts only for a sink that
        reads it."""
        sts = None
        for ops, sink in branches:
            st_items = None
            if sink.reads_subtask and subtasks is not None:
                if sts is None:
                    sts = subtasks.tolist()
                st_items = sts
            items = iter_rows(columns)
            if ops:
                items, st_items = _apply_ops_many(ops, items, st_items)
            self._rows_batched.inc(sink.emit_many(items, st_items))

    def _dispatch_inner(self, emissions, t_batch=None, step: int = -1) -> int:
        """Hand one step's fetched emissions to the sinks (or the next
        stage); returns the rows the sinks received."""
        # step epoch for host-evaluated fire ordering: the per-step
        # dispatch sequence is SPMD-identical across processes (the
        # fetch decision keys on GLOBAL emission counts), so it is a
        # valid leading component of the cross-process merge key
        self._dispatch_seq += 1
        emitted_before = self.metrics.records_emitted
        chained = self.downstream is not None or self._lazy_plans
        fire_info = emissions.get("process_fire")
        if fire_info is not None:
            n, fired = self.program.evaluate_fires(
                self.state, fire_info, self.plan.device_post, self._emit_row
            )
            if not chained:
                self.metrics.records_emitted += n
            self.metrics.window_fires += fired
            if fired:
                self.obs.counter("window_fires").inc(fired)
        main = emissions.get("main")
        if main is not None:
            sel, take, j_valid = self._stream_rows(main)
            if self._multiproc and self.downstream is not None:
                # multi-host chain: buffer the LOCAL rows with their
                # global order keys, even when this process has none
                # this step — pump_chain allgathers PER ENTRY, and the
                # collective call count must match on every process.
                # Window stages order by (end, key); rolling/count
                # stages order by global post-exchange row index, which
                # reconstructs the single-process hand-off order (each
                # process's rows ARE its shards' region of the global
                # row space). Compacted streams never reach here —
                # compaction is disabled under multi-host.
                cols = self._chain_cols([take(c) for c in main["cols"]])
                wend = main.get("window_end")
                if wend is not None:
                    self._chain_buf.append(("win", cols,
                        take(wend),
                        take(main["key"]),
                    ))
                else:
                    base = self._local_row_base(
                        np.asarray(main["mask"]).shape[0]
                    )
                    gorder = (j_valid + base).astype(np.int64)
                    tsarr = main.get("ts")
                    ets = (
                        take(tsarr)
                        if (self._chain_ts and tsarr is not None)
                        else None
                    )
                    self._chain_buf.append(("arr", cols, gorder, ets))
            elif sel.size:
                cols = [take(c) for c in main["cols"]]
                if self.downstream is not None:
                    cols = self._chain_cols(cols)
                    # chained stage: hand the columnar emissions straight
                    # to the next runner (no Python rows in between).
                    # Event timestamps: window results carry end - 1
                    # (Flink's window result timestamp), rolling
                    # aggregates forward the record timestamp.
                    wend = main.get("window_end")
                    kcol = main.get("key")
                    w_rows = take(wend) if wend is not None else None
                    if (
                        wend is not None
                        and kcol is not None
                        and self.program.n_shards > 1
                    ):
                        # canonical (end, key) order: sharded emission
                        # buffers stack per shard, which would reorder
                        # rows of DIFFERENT stage-1 keys that share a
                        # stage-2 key; the single-chip fire path emits
                        # end-major then key, so sort to match it
                        kk = take(kcol)
                        o = np.lexsort((kk, w_rows))
                        w_rows = w_rows[o]
                        cols = [c[o] for c in cols]
                    ts_rows = None
                    if self._chain_ts:
                        if wend is not None:
                            ts_rows = w_rows - 1
                        else:
                            ts_rows = take(main["ts"])
                    self._chain_buf.append((cols, ts_rows))
                    self._ledger_handed += int(sel.size)
                else:
                    subtask = main.get("subtask")
                    subtask = (
                        take(subtask) if subtask is not None else None
                    )
                    with self.obs.span("emit.format", step):
                        columns = self.formatter.columns(cols)
                    if chained or self._ledger_edges is not None:
                        # per row: a process() chain buffers each row,
                        # and the ledger accounts each one as it lands
                        sts = (
                            subtask.tolist() if subtask is not None
                            else itertools.repeat(None)
                        )
                        for row, st in zip(iter_rows(columns), sts):
                            self._emit_row(row, st)
                    else:
                        self._hand_off(self.sinks, columns, subtask)
                    self.metrics.records_emitted += sel.size
        late = emissions.get("late")
        if late is not None and self.side_sinks:
            self._dispatch_late(late)
        timeout = emissions.get("timeout")
        if timeout is not None:
            self._dispatch_timeout(timeout)
        emitted_delta = self.metrics.records_emitted - emitted_before
        if emitted_delta:
            self.obs.records_emitted.inc(emitted_delta)
        if t_batch is not None and emitted_delta:
            self.metrics.emit_latencies_s.append(
                time.perf_counter() - t_batch
            )
        return emitted_delta

    def _dispatch_late(self, late):
        # late-drop COUNTING happens on device (state["late_dropped"], so
        # jobs without a side output still observe drops); this path only
        # feeds the configured side sinks
        sel, take, _ = self._stream_rows(late)
        if not sel.size:
            return
        cols = [take(c) for c in late["cols"]]
        fmt = EmissionFormatter(
            self.program.mid_kinds, self.program.mid_tables
        )
        # the CEP timeout tag's sink receives ONLY the timeout stream
        tt = getattr(self.program, "timeout_tag", None)
        columns = fmt.columns(cols)
        for tag_id, branch in self.side_sinks.items():
            if tt is None or tag_id != tt.id:
                self._side_hand_off(tag_id, branch, columns)

    def _dispatch_timeout(self, timeout):
        """Route within()-expired partial matches to the pattern's
        timeout side output (Flink's PatternTimeoutFunction stream)."""
        tt = getattr(self.program, "timeout_tag", None)
        entry = self.side_sinks.get(tt.id) if tt is not None else None
        if entry is None:
            return
        sel, take, _ = self._stream_rows(timeout)
        if not sel.size:
            return
        cols = [take(c) for c in timeout["cols"]]
        fmt = EmissionFormatter(
            self.program.timeout_kinds, self.program.timeout_tables
        )
        self._side_hand_off(tt.id, entry, fmt.columns(cols))

    def _side_hand_off(self, tag_id, branch, columns):
        """Hand a side output's rows to its branch: in one call, or
        with the ledger on row by row, each row accounted as it
        lands."""
        edge = (
            self._ledger_side.get(tag_id)
            if self._ledger_side is not None else None
        )
        if edge is None:
            self._hand_off((branch,), columns)
            return
        ops, sink = branch
        for row in iter_rows(columns):
            item, keep = _apply_ops(ops, row)
            if keep:
                sink.emit(item)
            else:
                edge["filtered"] += 1
            edge["in"] += 1


def _reject_count_ts(st):
    """Count-window results carry no event timestamps (Flink's
    GlobalWindow has none), so they cannot feed event-time stages."""
    if st is not None and st.window is not None and st.window.kind == "count":
        raise NotImplementedError(
            "count-window results carry no event timestamps (Flink's "
            "GlobalWindow); window the chained stage in processing time, "
            "or use a time window upstream"
        )


def _chain_needs_event_ts(plans) -> bool:
    """True when any stage in ``plans`` windows in event time (its input
    records then need timestamps from the upstream stage)."""
    for p in plans:
        st = p.stateful
        if (
            st is not None
            and st.window is not None
            and st.window.time_domain == TimeCharacteristic.EventTime
            and st.window.is_time_window()
        ) or (
            st is not None
            and st.window is not None
            and st.window.kind == "session"
            and st.window.time_domain == TimeCharacteristic.EventTime
        ):
            return True
    return False


def _wire_chain_ts(up: Runner, down: Runner):
    """Mark ``up`` to extract per-row event timestamps for its chain when
    any downstream stage windows in event time, and validate the upstream
    program can provide them."""
    rest_plans = [r.plan for r in down.chain()]
    if not _chain_needs_event_ts(rest_plans):
        return
    up._chain_ts = True
    st = up.plan.stateful
    _reject_count_ts(st)
    if st is not None and st.kind in ("rolling", "rolling_reduce"):
        up.program.emit_ts = True  # read at trace time (first batch)


def _make_runner_chain(plans, cfg, metrics, lazy_schemas=None) -> Runner:
    """Build the runner for plans[0] plus downstream runners for any
    chained stages, wiring record schemas from each upstream program.

    A stage fed by a full-window process() stage resolves its schema
    from the user function's first collected rows (the function may emit
    any shape), so its runner is built lazily on the first pump — unless
    ``lazy_schemas`` (checkpoint restore) supplies the schema each such
    stage had already inferred, in which case the full chain builds
    eagerly with the snapshotted kinds/tables."""
    from ..records import StringTable

    lazy_schemas = list(lazy_schemas or [])
    runner = Runner(plans[0], cfg, metrics)
    up = runner
    for i, p2 in enumerate(plans[1:], start=1):
        if getattr(up.program, "host_evaluated", False):
            if lazy_schemas:
                saved = lazy_schemas.pop(0)
                p2.record_kinds.extend(saved["kinds"])
                last = len(saved["tables"]) - 1
                for ti, t in enumerate(saved["tables"]):
                    if t is None:
                        p2.tables.append(None)
                    else:
                        # a computed-key stage's trailing synthetic
                        # column restores as a DerivedKeyTable
                        table = (
                            DerivedKeyTable()
                            if p2.synthetic_key and ti == last
                            else StringTable()
                        )
                        table.load_state_dict(t)
                        p2.tables.append(table)
                r2 = Runner(p2, cfg, metrics)
                r2._lazy_schema = True
                up.chain_to(r2)
                up = r2
                continue
            up._lazy_plans = list(plans[i:])
            up._chain_ts = _chain_needs_event_ts(up._lazy_plans)
            if up._chain_ts:
                _reject_count_ts(up.plan.stateful)
            break
        p2.record_kinds.extend(up.program.out_kinds)
        p2.tables.extend(up.program.out_tables)
        if p2.synthetic_key:
            # computed KeySelector on this chain stage: the glue
            # derives the key from each hand-off batch into a trailing
            # synthetic column
            p2.record_kinds.append(STR)
            p2.tables.append(DerivedKeyTable())
        r2 = Runner(p2, cfg, metrics)
        up.chain_to(r2)
        st = up.plan.stateful
        if st is not None and st.window is not None and (
            st.window.is_time_window() or st.window.kind == "session"
        ):
            # emit the key column so the chain glue can impose the
            # canonical (end, key) order across shards (read at trace
            # time — the program jits on its first batch)
            up.program.emit_chain_key = True
        up = r2
    # wire ts extraction only once the FULL chain exists: whether stage i
    # must extract timestamps depends on every stage after it
    r = runner
    while r is not None and r.downstream is not None:
        _wire_chain_ts(r, r.downstream)
        r = r.downstream
    return runner


def _pulled(source_batches, tracer, timed: bool = True):
    """Number each batch pulled from the source with a fresh step id
    (``SourceBatch.step``). With ``timed`` the pull itself is the
    executor's wait for the batch: a ``source`` span for that step,
    whose duration lands in ``SourceBatch.wait_s``."""
    it = iter(source_batches)
    while True:
        sid = tracer.new_step()
        if timed:
            with tracer.span("source", sid), Stopwatch() as sw:
                sb = next(it, None)
        else:
            sb = next(it, None)
        if sb is None:
            return
        sb.step = sid
        if timed:
            sb.wait_s = sw.elapsed
        yield sb


def _prefetch_iter(it, depth: int, depth_gauge=None, tracer=NULL_TRACER):
    """Drain ``it`` on a daemon thread into a bounded queue (size =
    ``depth``): the producer blocks when the consumer falls behind
    (bounded memory, natural backpressure), and producer exceptions
    re-raise at the consumer. Used for StreamConfig.parse_ahead.
    ``depth_gauge`` (obs) reads the queue depth at snapshot time — a
    full queue means the device loop, not the parser, is the bottleneck.
    Each queue wait is the ``tracer``'s ``source`` span of the step
    whose prepared batch it returned (``SourceBatch.wait_s``)."""
    import queue as queue_mod
    import threading

    q: "queue_mod.Queue" = queue_mod.Queue(maxsize=max(1, depth))
    if depth_gauge is not None:
        depth_gauge.set_fn(q.qsize)
    stop = threading.Event()

    def put(item) -> bool:
        # bounded-put that gives up when the consumer abandoned the
        # generator (exception in the consuming loop): without the stop
        # check the producer would block on a full queue forever,
        # pinning the source iterator and parsed batches
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue_mod.Full:
                continue
        return False

    def run():
        try:
            for item in it:
                if not put(("item", item)):
                    return
            put(("done", None))
        except BaseException as e:  # surfaces in the consumer
            put(("err", e))

    threading.Thread(target=run, daemon=True).start()
    try:
        while True:
            with tracer.span("source") as sp, Stopwatch() as sw:
                kind, payload = q.get()
                if kind == "item":
                    sp.note(step=payload[0].step)
            if kind == "item":
                payload[0].wait_s = sw.elapsed
            if kind == "done":
                return
            if kind == "err":
                raise payload
            yield payload
    finally:
        stop.set()


def execute_job(env, sink_nodes) -> JobResult:
    """Run the job, supervised when a restart strategy is configured.

    With ``StreamConfig.restart_strategy`` set, failures route through
    runtime/supervisor.py: the strategy decides whether the job
    restarts, and a restart rebuilds the chain and resumes exactly-once
    from the latest valid checkpoint. Unset (the default), the first
    failure propagates exactly as before supervision existed."""
    # pre-flight static analysis (tpustream/analysis): runs ONCE per
    # submission, before supervision, planning, or any XLA trace. Under
    # strict_analysis an ERROR finding aborts the job here; otherwise
    # (obs on) the findings stash on the env and the first attempt's
    # _execute_job turns them into counters + flight breadcrumbs.
    if getattr(env.config, "strict_analysis", False) or env.config.obs.enabled:
        from ..analysis import PlanAnalysisError, analyze, has_errors

        findings = analyze(env, sink_nodes)
        if findings:
            env._analysis_findings = findings
        if getattr(env.config, "strict_analysis", False) and has_errors(findings):
            raise PlanAnalysisError(findings)
    # self-healing ingest plane (runtime/ingest.py): lane recovery keeps
    # the job running with no job restart, so surface it through the
    # same built-in WARN health-rule mechanism as job_restarted
    if env.config.ingest_lanes > 1 and env.config.obs.enabled:
        from .supervisor import _install_lane_restart_health_rule

        _install_lane_restart_health_rule(env)
        # resource plane (obs/resources.py): when the /proc sampler is
        # on, core contention between lane workers surfaces as the same
        # kind of built-in WARN transition
        if getattr(env.config.obs, "resources", False):
            from .supervisor import _install_lane_contention_health_rule

            _install_lane_contention_health_rule(env)
    # conservation ledger (obs/ledger.py): a latched invariant violation
    # is a correctness event, so the built-in rule is CRIT — installed
    # here (before JobObs reads health_rules) for supervised and plain
    # runs alike
    from ..obs.ledger import ledger_effective

    if ledger_effective(env.config.obs):
        from .supervisor import _install_ledger_health_rule

        _install_ledger_health_rule(env)
    # restore drills (runtime/checkpoint.py restore_drill): a failed
    # dry-restore of the nominal newest snapshot is a WARN, repeated
    # failures CRIT — installed here so the rules exist before JobObs
    # reads health_rules, supervised and plain runs alike
    if (
        env.config.restore_drill_interval_s > 0
        and env.config.obs.enabled
        and bool(env.config.checkpoint_dir)
        and env.config.checkpoint_interval_batches > 0
    ):
        from .supervisor import _install_restore_drill_health_rules

        _install_restore_drill_health_rules(env)
    if getattr(env.config, "restart_strategy", None) is not None:
        from .supervisor import supervise

        return supervise(env, sink_nodes, _run_attempt)
    return _run_attempt(env, sink_nodes)


def _run_attempt(env, sink_nodes) -> JobResult:
    """One execution attempt; on ANY failure, write the flight-recorder
    postmortem (terminal exception + the operator that was active + the
    event ring) before re-raising. ``env.metrics`` is installed as soon
    as the Metrics facade exists, so even a crashed job leaves its
    partial counters readable."""
    try:
        result = _execute_job(env, sink_nodes)
    except BaseException as e:
        job_obs = getattr(getattr(env, "metrics", None), "job_obs", None)
        if job_obs is not None:
            # a supervised attempt may restart: the postmortem dump is
            # the SUPERVISOR's call (written only when it gives up), not
            # every failed attempt's — a recovered job must not litter
            # cwd with "failed" dumps
            job_obs.on_failure(
                e, dump=getattr(env, "_supervision", None) is None
            )
        raise
    finally:
        # sharded ingestion clean-up: lane workers and their shared-
        # memory rings die with the attempt, crashed or not, so a
        # supervised restart never leaks a worker fleet per attempt
        plane = env.__dict__.pop("_ingest_plane", None)
        if plane is not None:
            plane.close()
        # checkpoint-plane clean-up: join the writer thread (an
        # in-flight write may land — completed snapshots are always
        # consistent); a writer failure is NOT re-raised here — either
        # it already crossed at a submit/flush, or the attempt is
        # failing for its own reason, which stays the reported cause
        ck_plane = env.__dict__.pop("_checkpoint_plane", None)
        if ck_plane is not None:
            ck_plane.close(raise_error=False)
    job_obs = getattr(env.metrics, "job_obs", None)
    if job_obs is not None:
        job_obs.close()
    return result


def _execute_job(env, sink_nodes) -> JobResult:
    # effective-config resolution (StreamConfig.resolve): cross-knob
    # clamps applied once here; env.config keeps the requested values
    cfg, resolve_notes = env.config.resolve()
    plans = build_plan_chain(env, sink_nodes)
    plan = plans[0]
    chained = len(plans) > 1
    host = HostStage(plan, cfg)
    # supervised execution (runtime/supervisor.py): cross-attempt state —
    # the shared flight ring, cumulative restart counters to re-seed,
    # and the session nonce checkpoints are stamped with
    supervision = getattr(env, "_supervision", None)
    if cfg.obs.enabled:
        from ..obs.flightrecorder import jsonable_config
        from ..obs.runtime import JobObs

        job_obs = JobObs(
            cfg.obs,
            job_name=env.job_name or "job",
            flight=supervision.flight if supervision is not None else None,
        )
        metrics = Metrics(registry=job_obs.registry, job_name=job_obs.job_name)
        metrics.job_obs = job_obs
        if supervision is not None:
            supervision.seed_metrics(job_obs)
        # fleet runs (tenancy/server.py): wire the JobServer into the
        # obs root — per-tenant admission/error/step-share gauges refresh
        # at each snapshot tick, tenant SLOs land as health rules, and
        # /tenants.json gets its provider
        if getattr(env, "_tenancy", None) is not None:
            job_obs.attach_tenancy(env._tenancy)
        # first flight event: the exact resolved config — every
        # postmortem starts from the knobs the job actually ran with
        job_obs.flight.record(
            "config_resolved",
            job=job_obs.job_name,
            config=jsonable_config(cfg),
        )
    else:
        metrics = Metrics()
        job_obs = metrics.job_obs  # the null twin
    host.tracer = job_obs.tracer
    host.new_ids = job_obs.counter("intern_new_ids_total")
    # one breadcrumb per resolution clamp (every attempt: the resolved
    # knobs are part of this attempt's story, like config_resolved)
    for note in resolve_notes:
        job_obs.flight.record("config_clamped", **note)
    # native parser status: when the Makefile/g++ build failed (or the
    # .so is stale) the job silently runs the numpy parse path — leave
    # a breadcrumb so a postmortem explains the throughput cliff
    if job_obs.enabled:
        from .. import native as _native_mod

        if not _native_mod.available():
            job_obs.flight.record(
                "native_parse_unavailable",
                error=_native_mod.build_error() or "build not attempted",
            )
        else:
            # name the build flavor (default vs asan sanitizer kernel)
            # so a postmortem shows which _fastparse variant ran
            job_obs.flight.record(
                "native_parse_ready",
                flavor=_native_mod.build_flavor(),
            )
    # pre-flight analysis findings (stashed by execute_job; popped so a
    # supervised restart doesn't double-count): WARN/ERROR go to the
    # flight ring, every finding increments the per-code counter
    pending_findings = env.__dict__.pop("_analysis_findings", None)
    if pending_findings and job_obs.enabled:
        for f in pending_findings:
            job_obs.group.group(code=f.code).counter(
                "analysis_findings_total"
            ).inc()
            if f.severity in ("error", "warn"):
                job_obs.flight.record(
                    "analysis_finding",
                    code=f.code,
                    severity=f.severity,
                    node=repr(f.node) if f.node is not None else None,
                    message=f.message,
                )
    # dead-letter quarantine output (StreamConfig.dead_letter); lives on
    # the env so it survives restarts and the user reads it after execute
    dead_letters = getattr(env, "dead_letters", None)
    if dead_letters is None and cfg.dead_letter:
        dead_letters = env.dead_letters = []
    # conservation ledger (obs/ledger.py): per-edge record accounting +
    # per-sink digest anchors, one per attempt alongside JobObs. Its
    # refresh rides the snapshotter pre-hooks so residual gauges are
    # evaluated at exactly the snapshot cadence (and once at close).
    ledger = None
    from ..obs.ledger import ledger_effective

    if ledger_effective(cfg.obs):
        if jax.process_count() == 1:
            from ..obs.ledger import ConservationLedger

            ledger = ConservationLedger(
                job_obs, digests=getattr(cfg.obs, "ledger_digests", True)
            )
            job_obs.ledger = ledger
            job_obs.snapshotter.ledger = ledger
            job_obs.snapshotter.pre_hooks.append(ledger.refresh)
            if dead_letters is not None:
                ledger.register_dead_letters(dead_letters)
            if cfg.ingest_lanes > 1:
                # sharded ingestion parses in lane worker processes the
                # parent's host-op counters can't see — the source edge
                # degrades to informational; sink/chain/contents edges
                # (all parent-side) stay exact
                ledger.source_exact = False
                ledger.source_note = (
                    "sharded ingestion: host-side terms are partial, "
                    "residual not evaluated"
                )
            else:
                host.ledger_counts = {
                    "dropped": 0, "fm_in": 0,
                    "fm_out": 0, "quarantined": 0,
                }
        else:
            # local counts are partial under multi-host SPMD — a ledger
            # would report garbage residuals on every edge
            job_obs.flight.record("ledger_disabled", reason="multiprocess")
    # seeded fault-injection hook (tpustream/testing/faults.py): the
    # injector object outlives restart attempts, so occurrence counters
    # keep counting across rebuilds
    injector = cfg.extra.get("fault_injector") if cfg.extra else None
    fault = injector.check if injector is not None else None
    # scratch restart (no checkpoint to restore): recovery ends when the
    # rebuilt attempt starts; checkpointed restarts observe this in the
    # restore block below instead, after state is back on device
    if (
        supervision is not None
        and getattr(env, "_recovery_t0", None) is not None
        and not getattr(env, "_checkpoint_restore_path", None)
    ):
        job_obs.histogram("recovery_wall_ms").observe(
            (time.perf_counter() - env._recovery_t0) * 1000.0
        )
        env._recovery_t0 = None
    # installed BEFORE the run so the failure wrapper (and the user, via
    # env) can reach the partial metrics of a crashed job; the facade
    # mutates in place from here on
    env.metrics = metrics
    # host-side watermark gauges: fed per batch from the job's periodic
    # timestamp assigner (Flink's currentInputWatermark / watermark-lag
    # operator metrics). The device carries the authoritative clock; this
    # mirrors the host bookkeeping the reference documents, and stays
    # nonzero DURING the run (the device copy reads 0 lag after the
    # end-of-stream MAX watermark).
    assigner = plan.ts_assigner
    wm_gauge = lag_gauge = None
    if (
        job_obs.enabled
        and assigner is not None
        and hasattr(assigner, "observe")
        and hasattr(assigner, "get_current_watermark")
    ):
        wm_gauge = job_obs.gauge("watermark_ms")
        lag_gauge = job_obs.gauge("watermark_lag_ms")
    if job_obs.enabled:
        job_obs.gauge("source_queue_depth").set_fn(plan.source.queue_depth)
    runner: Optional[Runner] = None
    proc_now = 0
    domain = plan.time_characteristic

    # -- checkpoint restore (chapter3/README.md:454-456 teased surface) ----
    skip_lines = 0
    restore_path = getattr(env, "_checkpoint_restore_path", None)
    if restore_path:
        from .checkpoint import load_checkpoint

        ck = load_checkpoint(restore_path)
        ck.restore_tables(plan)
        if plan.rules is not None and ck.rule_values is not None:
            # sync the host RuleSet to the snapshot's rule timeline
            # BEFORE programs build: init_state seeds the rule leaves
            # from it, and the control-feed cursor (= version) skips the
            # already-applied schedule prefix during replay. In tenant
            # mode this also restores capacity + per-tenant vectors
            # (rule_values["__tenant__"]).
            plan.rules.load(ck.rule_values, ck.rule_version)
        if ck.tenancy is not None and getattr(env, "_tenancy", None) is not None:
            # the JobServer's host fleet state (tenant->slot map,
            # admitted/quota counters) restores alongside the vectors
            env._tenancy.load_state_dict(ck.tenancy)
        runner = _make_runner_chain(
            plans, cfg, metrics, lazy_schemas=ck.lazy_schemas
        )
        stages = runner.chain()
        # dynamic key growth may have left a stage running above its
        # configured capacity at snapshot time — rebuild UP to match.
        # (A capacity configured above the snapshot's wins: restore
        # grows the saved rows instead, never shrinking a user's
        # headroom into repeated re-growth.)
        for r, cap in zip(stages, ck.key_capacities or []):
            if cap and cap > r.cfg.key_capacity:
                r._grow_key_capacity(cap, cause="config_change")
        # computed-KeySelector chain stages intern into runtime-built
        # DerivedKeyTables — reload their snapshots so saved state rows
        # keep their key ids
        for r, t in zip(stages, ck.chain_key_tables or []):
            if t is not None and r.plan.synthetic_key and r.plan.tables:
                r.plan.tables[-1].load_state_dict(t)
        states = ck.restore_chain([r.program for r in stages])
        for r, s in zip(stages, states):
            r.state = s
            r.snapshot_counter_baseline()
        skip_lines = ck.source_pos
        proc_now = ck.proc_now
        if supervision is not None:
            # Roll buffered outputs back to the snapshot so the replayed
            # suffix lands exactly once. Collect handles truncate to the
            # checkpoint's recorded lengths when it was written by THIS
            # supervised session (nonce match); an older or manual
            # checkpoint's counts describe some other process's handles,
            # so those fall back to the supervisor's pre-job baselines.
            # Unsupervised restores (a fresh env resuming manually)
            # never truncate — the user owns the handle contents.
            handles = [
                n.params["handle"]
                for n in sink_nodes
                if n.op == "sink_collect"
            ]
            same_session = (
                ck.session is not None and ck.session == supervision.nonce
            )
            counts = (
                list(ck.sink_counts)
                if same_session and ck.sink_counts is not None
                else list(supervision.base_counts)
            )
            for h, keep in zip(handles, counts):
                del h.items[keep:]
            if dead_letters is not None:
                keep_dead = (
                    ck.quarantined if same_session else supervision.base_dead
                )
                del dead_letters[keep_dead:]
                metrics.records_quarantined = len(dead_letters)
            if ledger is not None:
                # the truncated persistent sinks must now MATCH the
                # snapshot's digest anchors: re-derive each digest over
                # the rolled-back contents and verify (same-session
                # anchors only — an older session's anchors describe
                # another process's contents), then re-anchor every
                # account so post-restore accounting starts clean
                ledger.on_restore(ck.ledger, verify=same_session)
            # recovery accounting: batches the resumed run replays
            # (skips) to reach the snapshot, and wall time from failure
            # detection (incl. the restart delay) to restored state
            supervision.replay_batches_total += ck.batches
            job_obs.counter("recovery_replay_batches").set_total(
                supervision.replay_batches_total
            )
            t0 = getattr(env, "_recovery_t0", None)
            if t0 is not None:
                job_obs.histogram("recovery_wall_ms").observe(
                    (time.perf_counter() - t0) * 1000.0
                )
                env._recovery_t0 = None
            job_obs.flight.record(
                "job_restored",
                checkpoint=restore_path,
                batches=ck.batches,
                emitted=ck.emitted,
                source_pos=ck.source_pos,
            )
    lines_consumed = skip_lines
    # -- dynamic rules (tpustream/broadcast): the control feed -------------
    ruleset = plan.rules
    control_feed = None
    if plan.broadcast is not None and ruleset is not None:
        if not restore_path:
            # a from-scratch (re)start replays data from record 0, so
            # the rule timeline replays with it: back to the declared
            # defaults at version 0, and the feed re-applies every
            # update at its original record boundary
            ruleset.reset()
        control_feed = plan.broadcast.feed(cfg.batch_size)
    # perf_counter at the last rule application; the next non-empty feed
    # closes the propagation window (bench.py phase U reads the series)
    rule_apply_t0: List[Optional[float]] = [None]

    def _apply_rule_updates(updates):
        """Land a group of rule updates atomically at the current record
        boundary: barrier the chain so every pre-update step retires,
        bump the host RuleSet, and swap the device rule leaves on every
        stage — buffer swaps, never a recompile."""
        rule_apply_t0[0] = time.perf_counter()
        runner.drain_chain(proc_now)
        old_version = ruleset.version
        for u in updates:
            ruleset.apply(u)
        for r in runner.chain():
            r.refresh_rules()
        tenant_slots = sorted(
            {
                u.tenant for u in updates
                if getattr(u, "tenant", None) is not None
            }
        )
        if fault is not None:
            # the crash window between rule application and the next
            # data batch: recovery must re-apply the update at the same
            # record boundary for byte-identical output
            fault("control_apply")
            if tenant_slots:
                # narrower window for the tenancy playbook: only fires
                # when a TENANT-scoped update (add/remove/update_rules)
                # was in the applied group
                fault("tenant_apply")
        job_obs.gauge("rule_version").set(ruleset.version)
        job_obs.counter("rule_updates_total").inc(len(updates))
        if job_obs.enabled and tenant_slots:
            from ..broadcast.rules import TENANT_ACTIVE_RULE, _to_bool

            srv = getattr(env, "_tenancy", None)
            # a falsy __tenant_active__ update IS tenant removal: those
            # slots get their per-tenant series retired, not re-minted —
            # a removed tenant's gauges must not linger in scrapes
            removed = {
                u.tenant for u in updates
                if getattr(u, "tenant", None) is not None
                and u.name == TENANT_ACTIVE_RULE
                and not _to_bool(u.value)
            }
            for slot in tenant_slots:
                if slot in removed:
                    continue
                label = (
                    srv.tenant_label(slot) if srv is not None else str(slot)
                )
                job_obs.group.group(tenant=label).gauge(
                    "tenant_rule_version"
                ).set(ruleset.version)
            if removed and srv is not None:
                for slot in sorted(removed):
                    srv.retire_tenant_obs(slot, job_obs)
        job_obs.flight.record(
            "rule_applied",
            old_version=old_version,
            new_version=ruleset.version,
            rules={u.name: ruleset.value(u.name) for u in updates},
            tenants=tenant_slots or None,
        )

    def _feed_measured(b, wm_low, t0, step=None):
        runner.feed(b, wm_low, t_batch=t0, step=step)
        if rule_apply_t0[0] is not None and b.n:
            job_obs.histogram("rule_update_propagation_ms").observe(
                (time.perf_counter() - rule_apply_t0[0]) * 1000.0
            )
            rule_apply_t0[0] = None

    ckpt_every = cfg.checkpoint_interval_batches
    ckpt_enabled = bool(cfg.checkpoint_dir) and ckpt_every > 0
    # async checkpoint plane (runtime/checkpoint.py CheckpointPlane):
    # the barrier pays capture only; encode + write + prune + GC run on
    # one background writer thread. Coordinator-only — non-coordinator
    # processes still capture (the gather is collective) and drop the
    # cut, matching the sync path's early return.
    is_coordinator = jax.process_index() == 0
    ckpt_plane = None
    if ckpt_enabled and cfg.checkpoint_async and is_coordinator:
        from .checkpoint import CheckpointPlane

        ckpt_plane = CheckpointPlane(
            cfg.checkpoint_dir,
            keep=cfg.checkpoint_keep,
            keep_every=cfg.checkpoint_keep_every,
            inflight=cfg.checkpoint_async_inflight,
            incremental=cfg.checkpoint_incremental,
            fault=fault,
        )
        # _run_attempt's finally pops and closes this, so a crashed
        # attempt never leaks a writer thread (and an in-flight write
        # is allowed to land — completed snapshots are consistent)
        env._checkpoint_plane = ckpt_plane

    def _note_checkpoint_report(rep: dict) -> None:
        """One completed write's report -> the metrics/flight surface.
        Main-thread only: async reports cross over via drain_reports."""
        if "write_wall_ms" in rep:
            job_obs.histogram("checkpoint_write_wall_ms").observe(
                rep["write_wall_ms"]
            )
        job_obs.histogram("checkpoint_bytes").observe(rep["bytes_total"])
        job_obs.histogram("checkpoint_bytes_delta").observe(
            rep["bytes_delta"]
        )
        job_obs.counter("checkpoint_chunks_reused_total").inc(
            rep["chunks_reused"]
        )
        if rep["gc_deleted"]:
            job_obs.counter("checkpoint_gc_deleted_total").inc(
                rep["gc_deleted"]
            )
        job_obs.flight.record(
            "checkpoint_saved",
            path=rep["path"],
            batches=rep["batches"],
            source_pos=rep["source_pos"],
            write_ms=round(rep.get("write_wall_ms", 0.0), 3),
            bytes_delta=rep["bytes_delta"],
            chunks_reused=rep["chunks_reused"],
            # environment stamp (obs/resources.py): a restored run
            # can prove what host/backend wrote the snapshot
            env=job_obs.env_compact(),
        )

    def _capture_cut():
        """One consistent cut at the checkpoint barrier. Emissions
        still in flight belong to pre-snapshot batches — a resume
        replays only post-snapshot lines — so they flush down the whole
        chain first; sink counts and ledger anchors are then exact as
        of this cut (not of write completion)."""
        from .checkpoint import capture_checkpoint

        runner.drain_chain(proc_now)
        stages = runner.chain()
        emitted = metrics.records_emitted
        if jax.process_count() > 1:
            # each process emits only its shards' records; the
            # snapshot records the GLOBAL count (the capture is
            # already a collective, so this gather aligns)
            from jax.experimental import multihost_utils as mh

            emitted = int(
                mh.process_allgather(
                    np.asarray([emitted], np.int64)
                ).sum()
            )
        lazy_schemas = [
            {
                "kinds": list(r.plan.record_kinds),
                "tables": [
                    t.state_dict() if t is not None else None
                    for t in r.plan.tables
                ],
            }
            for r in stages
            if getattr(r, "_lazy_schema", False)
        ]
        return capture_checkpoint(
            lazy_schemas=lazy_schemas,
            key_capacities=[r.cfg.key_capacity for r in stages],
            # only non-lazy CHAIN stages need this: stage 0's
            # derived table rides meta["tables"], lazy stages'
            # ride lazy_schemas
            chain_key_tables=[
                r.plan.tables[-1].state_dict()
                if si > 0
                and r.plan.synthetic_key
                and not getattr(r, "_lazy_schema", False)
                and r.plan.tables
                else None
                for si, r in enumerate(stages)
            ],
            state=(
                [r.state for r in stages]
                if len(stages) > 1
                else runner.state
            ),
            plan=plan,
            source_pos=lines_consumed,
            proc_now=proc_now,
            emitted=emitted,
            batches=metrics.batches,
            job_name=env.job_name,
            parallelism=max(1, cfg.parallelism),
            # supervised-recovery metadata: collect-sink lengths
            # at the snapshot (output rollback on restore),
            # quarantine high-water mark, and the supervision
            # session nonce that scopes both
            sink_counts=[
                len(n.params["handle"].items)
                for n in sink_nodes
                if n.op == "sink_collect"
            ],
            quarantined=(
                len(dead_letters) if dead_letters is not None else 0
            ),
            session=(
                supervision.nonce if supervision is not None else None
            ),
            # dynamic rules: the host RuleSet's values + applied-
            # update count at the snapshot — restore re-syncs the
            # control-feed cursor from these (broadcast/rules.py)
            rule_values=(
                ruleset.values() if ruleset is not None else None
            ),
            rule_version=(
                ruleset.version if ruleset is not None else 0
            ),
            # multi-tenancy: the JobServer's host fleet state
            # (tenant->slot map, admitted/quota counters); the
            # per-tenant rule vectors ride rule_values above
            tenancy=(
                env._tenancy.state_dict()
                if getattr(env, "_tenancy", None) is not None
                else None
            ),
            # sharded ingestion: the per-lane frame cursor at
            # this snapshot (frames the merge consumed; frames
            # still in a lane ring are not in source_pos either,
            # so recovery replays them exactly once)
            ingest=(
                ingest_plane.cursor()
                if ingest_plane is not None
                else None
            ),
            # conservation ledger: per-sink (count, digest)
            # anchors at this barrier — a supervised restore
            # re-derives and verifies them over the truncated
            # sinks (obs/ledger.py). The drain above makes
            # these exact: all consumed batches have landed.
            ledger=(
                ledger.anchors() if ledger is not None else None
            ),
        )

    # restore drills (runtime/checkpoint.py restore_drill): time-gated
    # dry restore of the nominal newest snapshot — format + chunk-chain
    # walk, layout audit, ledger anchor re-derivation — so bit-rot is a
    # health transition before a crash needs the snapshot
    drill_interval = cfg.restore_drill_interval_s
    drill_last = [time.monotonic()]

    def _maybe_restore_drill() -> None:
        if (
            drill_interval <= 0
            or not ckpt_enabled
            or not is_coordinator
            or time.monotonic() - drill_last[0] < drill_interval
        ):
            return
        drill_last[0] = time.monotonic()
        from .checkpoint import restore_drill
        from .supervisor import _layout_audit

        with Stopwatch() as dr_sw:
            res = restore_drill(
                cfg.checkpoint_dir,
                audit=_layout_audit(env, sink_nodes, job_obs.flight),
                verify_anchors=(
                    ledger.verify_anchors if ledger is not None else None
                ),
            )
        if res["ok"] is None:
            return  # nothing to drill yet
        job_obs.histogram("restore_drill_ms").observe(dr_sw.elapsed * 1000.0)
        job_obs.gauge("restore_drill_verdict").set(1.0 if res["ok"] else 0.0)
        if not res["ok"]:
            job_obs.counter("restore_drill_failures_total").inc()
            job_obs.flight.record(
                "restore_drill_failed",
                path=res["path"],
                reason=res["reason"],
            )
    # Emission pipelining helps only when batches arrive back to back; a
    # PACED source (steady-rate feed with idle gaps) would otherwise see
    # its results parked in the in-flight window for async_depth batch
    # intervals — latency inflating as the rate drops. When the time
    # spent WAITING INSIDE THE SOURCE for the next batch exceeds one
    # pipelining quantum, fetch synchronously: the link is idle anyway.
    # (The wait is measured from the end of the previous loop body to
    # the source's yield — NOT feed-to-feed wall time, which includes
    # batch processing and misreads a slow link's flood as paced,
    # forcing a full drain every batch.)
    t_iter_done: Optional[float] = None
    IDLE_GAP_S = 0.05
    # markers from source batches that carried no feedable data yet
    # (idle ticks, pre-first-batch); they attach to the next real step
    marker_backlog: List = []
    # previous host watermark, for the flight recorder's jump detector
    wm_prev: Optional[int] = None
    STALL_GAP_S = 1.0  # source gaps beyond this become flight events

    def wm_lower_for_records(wm_hint: Optional[int]) -> int:
        if domain == TimeCharacteristic.ProcessingTime:
            return proc_now - 1
        if wm_hint is not None:
            return wm_hint
        return LONG_MIN + 1

    skip_state = [skip_lines]

    def _prepare(sb):
        """Resume line-skip + host parse for one source batch — the
        host stage. Runs inline, or on the parse-ahead thread
        (StreamConfig.parse_ahead), which sequences these calls itself,
        so skip_state stays single-writer either way."""
        if skip_state[0] > 0 and sb.n_records:
            # resume: drop source lines the checkpointed run already consumed
            take = min(skip_state[0], sb.n_records)
            if sb.raw is not None:
                if take == sb.n_raw:
                    rest = b""
                else:
                    off = 0
                    for _ in range(take):
                        off = sb.raw.index(b"\n", off) + 1
                    rest = sb.raw[off:]
                sb = SourceBatch(
                    [], sb.proc_ts[take:], sb.advance_proc_to, sb.final,
                    raw=rest, n_raw=sb.n_raw - take, markers=sb.markers,
                    step=sb.step, wait_s=sb.wait_s,
                )
            else:
                sb = SourceBatch(
                    sb.lines[take:], sb.proc_ts[take:], sb.advance_proc_to,
                    sb.final, markers=sb.markers, step=sb.step,
                    wait_s=sb.wait_s,
                )
            skip_state[0] -= take
        batch = wm_hint = None
        # parse spans may record from the parse-ahead thread; the
        # tracer's ring append is GIL-safe for this single extra writer
        with job_obs.tracer.span("parse", sb.step), Stopwatch() as hw:
            if fault is not None:
                fault("parse")
            try:
                batch, wm_hint = _parse(sb)
            except Exception as e:
                # poison-record quarantine (StreamConfig.dead_letter):
                # divert the bad lines, keep the stream alive. Injected
                # faults escalate — they model a crash, not bad data.
                if dead_letters is None or getattr(e, "fault_injection", False):
                    raise
                batch, wm_hint = _quarantine(sb, e)
        if ledger is not None:
            # ONE atomic commit per batch (offered is post-resume-trim):
            # the parse-ahead thread owns these terms and the snapshot
            # evaluator reads under the same lock, so a refresh landing
            # mid-batch never sees a torn offered/admitted cut
            ledger.account_source(
                offered=sb.n_records,
                admitted=batch.n if batch is not None else 0,
                host=host.ledger_counts,
            )
        return sb, batch, wm_hint, hw

    def _parse(sb):
        if sb.raw is not None:
            batch, wm_hint = host.process_raw(sb.raw, sb.n_raw, sb.proc_ts,
                                              sb.step)
            if batch is None and sb.n_raw:
                # native lane unavailable: decode and take the line path
                batch, wm_hint = host.process(_raw_lines(sb), sb.proc_ts)
            return batch, wm_hint
        return host.process(sb.lines, sb.proc_ts)

    def _raw_lines(sb):
        lines = sb.raw.decode("utf-8", "replace").split("\n")
        if len(lines) == sb.n_raw + 1 and lines[-1] == "":
            lines.pop()  # trailing newline
        if len(lines) != sb.n_raw:
            raise ValueError(
                f"raw source batch declares {sb.n_raw} lines "
                f"but contains {len(lines)}"
            )
        return lines

    def _quarantine(sb, err):
        """Re-parse a failed batch line by line: lines that parse feed
        the device as one (smaller) batch, lines that don't land in
        ``env.dead_letters`` as ``(line, error)`` pairs — bounded by
        ``dead_letter_capacity`` (the counter keeps counting past it)."""
        lines = _raw_lines(sb) if sb.raw is not None else sb.lines
        good: List[str] = []
        good_idx: List[int] = []
        bad = 0
        first_err = None
        # per-line probe parses must not commit host-op ledger terms:
        # the probe AND the final reparse of the good lines would count
        # every filter/flat_map twice (process() commits on success)
        saved_counts = host.ledger_counts
        host.ledger_counts = None
        try:
            for i, line in enumerate(lines):
                try:
                    host.process([line], sb.proc_ts[i : i + 1])
                except Exception as line_err:
                    bad += 1
                    first_err = (
                        first_err if first_err is not None else line_err
                    )
                    if len(dead_letters) < cfg.dead_letter_capacity:
                        entry = (
                            line, f"{type(line_err).__name__}: {line_err}"
                        )
                        if ledger is not None:
                            # append + digest-fold atomically, so the
                            # contents edge never sees one without the
                            # other (this runs on the parse-ahead thread)
                            ledger.note_dead_letter(dead_letters, entry)
                        else:
                            dead_letters.append(entry)
                else:
                    good.append(line)
                    good_idx.append(i)
        finally:
            host.ledger_counts = saved_counts
        if not bad:
            # the batch failed as a whole but every line parses alone —
            # a genuine batch-level error, not poison data: escalate
            raise err
        if saved_counts is not None:
            # every bad line leaves the stream here — counted even past
            # dead_letter_capacity, like records_quarantined below
            saved_counts["quarantined"] += bad
        metrics.records_quarantined += bad
        job_obs.flight.record(
            "records_quarantined",
            count=bad,
            total=int(metrics.records_quarantined),
            error=f"{type(first_err).__name__}: {str(first_err)[:200]}",
        )
        return host.process(
            good, sb.proc_ts[np.asarray(good_idx, dtype=np.int64)]
        )

    source_batches = plan.source.batches(cfg.batch_size, cfg.max_batch_delay_ms)
    if injector is not None:
        # source_read faults fire between batch pulls, before any
        # marker stamping — exactly where a real read error would
        source_batches = injector.wrap_source(source_batches)
    if job_obs.enabled and cfg.obs.latency_marker_interval_ms > 0:
        # e2e latency markers: stamped at the source, riding the same
        # pack/dispatch/fetch/emit path as records (obs/latency.py).
        # Not installed otherwise — the disabled path iterates the raw
        # source with no per-batch marker work at all.
        from ..obs.latency import MarkerStamper, stamp_markers

        _tenancy = getattr(env, "_tenancy", None)
        source_batches = stamp_markers(
            source_batches,
            MarkerStamper(
                cfg.obs.latency_marker_interval_ms,
                counter=job_obs.counter("latency_markers_emitted"),
                # fleet runs label markers round-robin over the active
                # tenants (bounded top-K + "__other__"); the terminal
                # runner lands them in tenant_e2e_latency_ms{tenant=...}
                tenant_provider=(
                    _tenancy.marker_tenant_provider()
                    if _tenancy is not None
                    else None
                ),
                # sampled record flight paths ride the same channel:
                # ~trace_sample_rate of records get a RecordTrace probe
                # collecting a span per hop (obs/tracing_export.py)
                trace_sample_rate=cfg.obs.trace_sample_rate,
                trace_counter=job_obs.counter(
                    "record_traces_sampled_total"
                ),
            ),
        )
    # sharded host ingestion (runtime/ingest.py): lane worker processes
    # parse frames in parallel; the merge point yields the SAME
    # (sb, batch, wm_hint, hw) tuples in sequence order, so everything
    # downstream — feed, H2D staging, checkpoints — is unchanged.
    # _run_attempt closes the plane (env._ingest_plane) on any exit.
    ingest_plane = None
    if cfg.ingest_lanes > 1:
        from .ingest import build_ingest_plane

        ingest_plane = env._ingest_plane = build_ingest_plane(
            host, cfg, plan, job_obs,
            single_process=jax.process_count() == 1,
            fault=fault, skip_lines=skip_lines,
        )
    prefetched = (
        cfg.parse_ahead > 0
        and jax.process_count() == 1
        and ingest_plane is None
    )
    # every pull numbers its batch's step; the executor's wait for the
    # batch is the ``source`` span: around the pull itself, or with
    # parse-ahead around the consumer's queue wait (below)
    source_batches = _pulled(source_batches, job_obs.tracer,
                             timed=not prefetched)
    if ingest_plane is not None:
        prepared = ingest_plane.frames(source_batches, _prepare)
        # per-lane CPU attribution (obs/resources.py): the sampler
        # re-reads the PID map at every tick, so lane respawns are
        # tracked without re-attachment
        resources = getattr(job_obs, "resources", None)
        if resources is not None:
            resources.attach_lanes(ingest_plane.lane_pids)
    else:
        prepared = map(_prepare, source_batches)
    if prefetched:
        # source + parse on their own thread (the reference's source-
        # operator thread): batch N+1 parses while N crosses the link
        prepared = _prefetch_iter(
            prepared,
            cfg.parse_ahead,
            depth_gauge=(
                job_obs.gauge("parse_ahead_queue_depth")
                if job_obs.enabled
                else None
            ),
            tracer=job_obs.tracer,
        )

    for sb, batch, wm_hint, hw in prepared:
        # idle reference: inline, parse START (hw.t0) — the wait inside
        # the source, EXCLUDING parse time (a slow parse must not read
        # as a paced gap); prefetched, the consumer-side wait (parse
        # overlaps, so time spent blocked on the queue IS source idle)
        now_ref = time.perf_counter() if prefetched else hw.t0
        src_gap = (
            now_ref - t_iter_done if t_iter_done is not None else 0.0
        )
        if sb.wait_s > STALL_GAP_S:
            # per-incident, not per-batch: a stalled source records one
            # event per observed gap (its ``source`` span), bounded by
            # the gap itself
            job_obs.flight.record(
                "source_stall", gap_s=round(sb.wait_s, 3),
                batches_consumed=metrics.batches, step=sb.step,
            )
        if sb.markers:
            for m in sb.markers:
                if getattr(m, "trace_id", 0):
                    # the main-loop parse (inline path) or seq-ordered
                    # merge (lane path) this batch just crossed
                    m.add_host_parse(hw.t0, hw.elapsed)
            marker_backlog.extend(sb.markers)
        lines_consumed += sb.n_records
        metrics.host_times_s.append(hw.elapsed)
        metrics.batches += 1
        if lag_gauge is not None and batch is not None and batch.ts is not None \
                and batch.ts.size:
            # per-batch host watermark bookkeeping (obs-gated): observe
            # the batch max, then read the monotone watermark + its lag
            assigner.observe(int(batch.ts.max()))
            wm_now = assigner.get_current_watermark().timestamp
            wm_gauge.set(wm_now)
            lag = getattr(assigner, "current_lag_ms", None)
            if lag is not None:
                lag_gauge.set(lag())
            if (
                wm_prev is not None
                and wm_now - wm_prev > cfg.obs.flight_watermark_jump_ms
            ):
                # the classic postmortem breadcrumb: a replay of old
                # data or an idle partition makes the watermark leap
                job_obs.flight.record(
                    "watermark_jump", from_ms=wm_prev, to_ms=wm_now,
                    jump_ms=wm_now - wm_prev,
                )
            wm_prev = wm_now
        job_obs.maybe_snapshot()
        if sb.proc_ts.size:
            proc_now = max(proc_now, int(sb.proc_ts.max()))
        if sb.advance_proc_to is not None:
            proc_now = max(proc_now, int(sb.advance_proc_to))
        if batch is not None:
            if runner is None:
                runner = _make_runner_chain(plans, cfg, metrics)
            # multi-host: the idle test is LOCAL wall clock, so one
            # process could drain (appending chain-buffer entries and
            # issuing gathers) while its peer keeps the step in flight —
            # a collective-sequence mismatch. Multi-host runs keep the
            # deterministic pipelined path instead.
            idle = (
                jax.process_count() == 1
                and t_iter_done is not None
                and src_gap > IDLE_GAP_S
            )
            if marker_backlog:
                runner.accept_markers(marker_backlog)
                marker_backlog = []
            wm_low = wm_lower_for_records(wm_hint)
            if control_feed is None:
                runner.feed(batch, wm_low, t_batch=hw.t0, step=sb.step)
            else:
                # split the batch at each pending update's record
                # boundary: rows before position N run under the old
                # rules, rows at/after N under the new — record-exact
                # and batch-size independent (docs/dynamic_rules.md)
                base = lines_consumed - sb.n_records
                cursor = 0
                # the pulled step id goes with the batch's first slice
                sid = [sb.step]
                for off, updates in control_feed.splits_for(
                    base, sb.n_records
                ):
                    # quarantined rows can shrink the parsed batch
                    # below the source count; clamp to real rows
                    off = min(off, batch.n)
                    if off > cursor:
                        _feed_measured(
                            batch.slice_rows(cursor, off), wm_low, hw.t0,
                            sid.pop() if sid else None,
                        )
                        cursor = off
                    _apply_rule_updates(updates)
                rest = batch.slice_rows(cursor, batch.n) if cursor else batch
                if rest.n or not cursor:
                    _feed_measured(rest, wm_low, hw.t0,
                                   sid.pop() if sid else None)
            if idle:
                runner.drain_inflight()
        elif (
            sb.advance_proc_to is not None
            and runner is not None
            and domain == TimeCharacteristic.ProcessingTime
        ):
            if marker_backlog:
                runner.accept_markers(marker_backlog)
                marker_backlog = []
            runner.flush(proc_now - 1)
        if runner is not None:
            runner.pump_chain(proc_now)
        if (
            ckpt_enabled
            and runner is not None
            and metrics.batches % ckpt_every == 0
        ):
            with Stopwatch() as ck_sw:
                with Stopwatch() as cap_sw:
                    pending = _capture_cut()
                if ckpt_plane is not None:
                    # hand the cut to the writer thread; a full queue
                    # makes this wait (the counted barrier stall), and
                    # a writer failure re-raises HERE with its original
                    # fault point intact
                    ckpt_plane.submit(pending)
                    job_obs.gauge("checkpoint_async_inflight").set(
                        float(ckpt_plane.inflight())
                    )
                elif is_coordinator:
                    from .checkpoint import write_snapshot

                    with Stopwatch() as wr_sw:
                        rep = write_snapshot(
                            cfg.checkpoint_dir,
                            pending,
                            keep=cfg.checkpoint_keep,
                            keep_every=cfg.checkpoint_keep_every,
                            incremental=cfg.checkpoint_incremental,
                            fault=fault,
                        )
                    rep["write_wall_ms"] = wr_sw.elapsed * 1000.0
                    _note_checkpoint_report(rep)
            # snapshot cost series (docs/observability.md):
            # checkpoint_save_ms is the BARRIER-side total — capture +
            # budget wait in async mode, capture + write in sync mode —
            # so async vs sync stall is directly comparable;
            # checkpoint_capture_ms isolates the capture itself
            job_obs.histogram("checkpoint_capture_ms").observe(
                cap_sw.elapsed * 1000.0
            )
            job_obs.histogram("checkpoint_save_ms").observe(
                ck_sw.elapsed * 1000.0
            )
        if ckpt_plane is not None:
            reports = ckpt_plane.drain_reports()
            if reports:
                for rep in reports:
                    _note_checkpoint_report(rep)
                job_obs.gauge("checkpoint_async_inflight").set(
                    float(ckpt_plane.inflight())
                )
        if (
            getattr(env, "_savepoint_requests", None)
            and runner is not None
            and cfg.checkpoint_dir
        ):
            # pinned self-contained snapshots on request (rescale /
            # migration artifacts) — written synchronously at the batch
            # boundary, exempt from retention and GC by name
            from .checkpoint import save_savepoint

            sp_requests = list(env._savepoint_requests)
            env._savepoint_requests.clear()
            sp_pending = _capture_cut()
            for sp_tag in sp_requests:
                sp_path = save_savepoint(
                    cfg.checkpoint_dir, sp_pending, tag=sp_tag
                )
                env.savepoints.append(sp_path)
                job_obs.flight.record(
                    "savepoint_written",
                    path=sp_path,
                    tag=sp_tag,
                    source_pos=lines_consumed,
                    batches=metrics.batches,
                )
        _maybe_restore_drill()
        t_iter_done = time.perf_counter()
        if sb.final:
            break

    # stream end (bounded replay OR a socket/iterator source closing):
    # Flink's source-function return emits a Long.MAX_VALUE watermark that
    # fires every remaining event-time window — match that here
    if runner is not None:
        if marker_backlog:
            # final markers ride the end-of-stream flush step
            runner.accept_markers(marker_backlog)
            marker_backlog = []
        if control_feed is not None:
            # updates positioned at/after the last record still apply —
            # they govern the EOS window fires deterministically
            eos_updates = control_feed.remaining(lines_consumed)
            if eos_updates:
                _apply_rule_updates(eos_updates)
        if domain == TimeCharacteristic.ProcessingTime:
            runner.flush(proc_now - 1)
        else:
            runner.flush(MAX_WATERMARK)
        runner.drain_inflight()
        # chained stages: push the final emissions down the chain, then
        # fire EVERYTHING still windowed (Flink's end-of-input MAX
        # watermark) — nothing more can arrive after EOS. pump_chain may
        # BUILD a process()-fed stage here (lazy schema), so re-check
        # downstream after each pump.
        r = runner
        while True:
            r.pump_chain(proc_now)
            d = r.downstream
            if d is None:
                break
            d.flush(MAX_WATERMARK)
            d.drain_inflight()
            r = d
        # markers that never met another step (EOS right behind them)
        # still record at every remaining edge — no marker is lost
        runner.settle_markers()
        r = runner
        while r is not None:
            r.finalize_metrics()
            r.check_strict()
            r = r.downstream

    if ckpt_plane is not None:
        # land every queued write before the job returns, and surface a
        # writer failure even when no later barrier submitted (a fault
        # with EOS right behind it must still fail the attempt)
        ckpt_plane.flush()
        for rep in ckpt_plane.drain_reports():
            _note_checkpoint_report(rep)
        job_obs.gauge("checkpoint_async_inflight").set(0.0)

    return JobResult(metrics)
