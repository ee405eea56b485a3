"""Windowed keyed aggregation as one jitted XLA program.

Covers the reference's window surface: tumbling/sliding time windows in
processing or event time with incremental ``reduce``/``aggregate``
(chapter2/.../ComputeCpuAvg.java:27-60, chapter3/.../BandwidthMonitor.java:32-41,
chapter3/.../BandwidthMonitorWithEventTime.java:45-55), bounded
out-of-orderness watermarks with late-drop (chapter3/README.md:195-213),
allowed lateness with per-arrival re-fire and late-data side output
(chapter3/README.md:209-228).

Execution model per step (SURVEY.md §7), tuned from per-op measurements
on v5e (the scatter/gather cost model in docs/architecture.md):

  1. masked pre-chain (map/filter) over the batch,
  2. watermark update: monotone ``max(max_seen - delay, clock_hint)``,
  3. late split against the PRE-batch watermark,
  4. state merge: sort by (slot, key) cell, segmented associative scan
     with the user combiner, then ONE int32 set-scatter per storage
     plane at segment tails. State lives as int32 "word planes"
     ``[n_slots, keys]`` (ops/wordplanes.py) because v5e emulates 64-bit
     scatters ~8x slower than 32-bit ones; leaves the post chain can
     never observe are pruned entirely (ops/liveness.py), and a reduce
     key column that the combiner passes through verbatim is
     reconstructed from the cell index instead of stored.
  5. fire: window ends that crossed the watermark fire IN ORDER, up to
     ``max_fires_per_step`` per step (the executor drains the rest on
     flush ticks). Each fire composes its panes DENSELY — a fold of
     dynamic row slices over the ring, O(panes * keys) sequential HBM
     reads, no large gathers — then finalizes, runs the post chain over
     all keys at once, and append-compacts surviving alerts into the
     fixed ``alert_capacity`` output buffer. A step fed no valid row
     (clock tick, end of stream) defers the ends whose alerts no longer
     fit that buffer; the executor drains them with further such steps.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..api.functions import as_callable
from ..api.timeapi import TimeCharacteristic
from ..records import BOOL, F64, I64, NUMPY_DTYPES, STR
from ..ops import liveness
from ..ops import panes as pane_ops
from ..ops.panes import W0
from ..ops.segments import (
    segment_tails,
    segmented_scan,
    sort_by_key,
)
from ..ops.wordplanes import pack_words, plane_dtypes, unpack_words
from .device import DeviceChain, unwrap_record, wrap_record
from .plan import JobPlan
from .step import BaseProgram


def _dummy_scalar(kind: str):
    if kind == F64:
        return jnp.asarray(1.0, dtype=jnp.float64)
    if kind == BOOL:
        return jnp.asarray(True)
    return jnp.asarray(0, dtype=jnp.int32 if kind == STR else jnp.int64)


class WindowProgram(BaseProgram):
    STATE_COMPONENT_KEYS = {"pane_ring": pane_ops.PANE_RING_STATE_KEYS}
    accepted_kinds = ("tumbling", "sliding")
    main_emission_prefix = True  # append-compacted alert buffer
    operator_name = "window"

    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        st = plan.stateful
        spec = st.window
        if spec.kind not in self.accepted_kinds:
            raise NotImplementedError(
                f"{spec.kind} windows use a dedicated program"
            )
        self.key_pos = plan.key_pos
        self.apply_kind = st.apply_kind
        if (
            spec.time_domain == TimeCharacteristic.EventTime
            and plan.time_characteristic == TimeCharacteristic.EventTime
            and plan.ts_assigner is None
            and not plan.upstream_supplies_ts
        ):
            raise RuntimeError(
                "event-time windows need assign_timestamps_and_watermarks "
                "before other operators (reference "
                "chapter3/.../BandwidthMonitorWithEventTime.java:29)"
            )
        self.allowed_lateness_ms = st.allowed_lateness_ms
        # Flink's numLateRecordsDropped counts only records NOT consumed
        # by a late side output; with a side output configured the
        # records are delivered, not dropped
        self.count_late_as_dropped = not plan.side_outputs
        self.domain = spec.time_domain
        if self.domain == TimeCharacteristic.EventTime:
            # ingestion time rides the event machinery with delay 0
            self.delay_ms = plan.ts_delay_ms
        else:
            # processing time: wm = max_proc_seen - 1 so a record at T
            # fires windows ending <= T (timer semantics)
            self.delay_ms = 1
        self.ring = self._make_ring(spec, cfg)
        # SPMD hooks: identity on a single chip, mesh collectives in the
        # sharded subclass (key state sharded over the "shards" axis)
        self.n_shards = 1
        self.local_key_capacity = cfg.key_capacity
        self._build_agg()
        if self.apply_kind == "process":
            # post ops run on the host over user-collected results
            self.post_chain = None
            self.out_kinds = list(self.result_kinds)
            self.out_tables = list(self.result_tables)
        else:
            self.post_chain = DeviceChain(
                plan.device_post, self.result_kinds, self.result_tables
            )
            self.out_kinds = self.post_chain.out_kinds
            self.out_tables = self.post_chain.out_tables
            self._analyze_columns()

    def _make_ring(self, spec, cfg):
        return pane_ops.make_ring_spec(
            spec.size_ms,
            spec.slide_ms,
            self.delay_ms,
            self.allowed_lateness_ms,
            cfg.pane_ring_slack,
        )

    # ------------------------------------------------------------------
    # aggregation plumbing: lift / combine / finalize on leaf tuples
    # ------------------------------------------------------------------
    def _build_agg(self) -> None:
        st = self.plan.stateful
        kinds, tables = self.mid_kinds, self.mid_tables
        if self.apply_kind == "reduce":
            fn = as_callable(st.apply_fn, "reduce")

            def lift(cols):
                return tuple(cols)

            def combine(a, b):
                ra = wrap_record(kinds, tables, list(a))
                rb = wrap_record(kinds, tables, list(b))
                out, _, _ = unwrap_record(fn(ra, rb))
                return tuple(out)

            def finalize(leaves):
                return tuple(leaves)

            self.acc_kinds = list(kinds)
            self._acc_tables = list(tables)
            self.result_kinds = list(kinds)
            self.result_tables = list(tables)
        elif self.apply_kind == "process":
            # handled by ProcessWindowProgram override
            raise NotImplementedError
        elif self.apply_kind == "aggregate":
            agg = st.apply_fn
            create = as_callable(agg, "create_accumulator")
            add = as_callable(agg, "add")
            merge = as_callable(agg, "merge")
            get_result = as_callable(agg, "get_result")

            # infer accumulator layout from one concrete add
            probe_rec = wrap_record(
                kinds, tables, [_dummy_scalar(k) for k in kinds]
            )
            probe_acc = add(probe_rec, create())
            _, acc_kinds, acc_tables = unwrap_record(probe_acc)
            self.acc_kinds = acc_kinds
            self._acc_tables = acc_tables

            def lift(cols):
                def one(scalars):
                    rec = wrap_record(kinds, tables, list(scalars))
                    out, _, _ = unwrap_record(add(rec, create()))
                    return tuple(out)

                return jax.vmap(one)(tuple(cols))

            def combine(a, b):
                ra = wrap_record(acc_kinds, acc_tables, list(a))
                rb = wrap_record(acc_kinds, acc_tables, list(b))
                out, _, _ = unwrap_record(merge(ra, rb))
                return tuple(out)

            def finalize(leaves):
                rec = wrap_record(acc_kinds, acc_tables, list(leaves))
                out, _, _ = unwrap_record(get_result(rec))
                return tuple(out)

            # result layout from a concrete probe
            res = get_result(
                wrap_record(acc_kinds, acc_tables, [_dummy_scalar(k) for k in acc_kinds])
            )
            _, rk, rt = unwrap_record(res)
            self.result_kinds = rk
            self.result_tables = rt
        else:
            raise NotImplementedError(self.apply_kind)
        self.lift = lift
        self.combine = combine
        self.finalize = finalize

    # ------------------------------------------------------------------
    # column analysis: prune dead accumulator leaves, reconstruct keys
    # ------------------------------------------------------------------
    def _analyze_columns(self) -> None:
        arity = len(self.acc_kinds)
        dummies = [_dummy_scalar(k) for k in self.acc_kinds]

        def result_probe(*acc_scalars):
            res = self.finalize(tuple(acc_scalars))
            outs, keep, _, _ = self.post_chain._record_fn(
                list(res), jnp.asarray(True)
            )
            return tuple(outs) + (keep,)

        def combine_probe(*ab):
            return self.combine(tuple(ab[:arity]), tuple(ab[arity:]))

        live = liveness.live_accumulator_leaves(
            result_probe, combine_probe, dummies, arity
        )
        self.live_idx = [i for i, l in enumerate(live) if l]
        # reduce keeps records: the key leaf is reconstructable from the
        # cell index when the combiner passes it through verbatim
        self.key_leaf: Optional[int] = None
        if self.apply_kind == "reduce":
            passthrough = liveness.passthrough_outputs(
                combine_probe, dummies + dummies, arity
            )
            if self.key_pos in self.live_idx and passthrough[self.key_pos]:
                self.key_leaf = self.key_pos
        self.stored_idx = [i for i in self.live_idx if i != self.key_leaf]
        self.stored_kinds = [self.acc_kinds[i] for i in self.stored_idx]
        ops = liveness.leaf_algebraic_ops(combine_probe, dummies, arity)
        self.stored_ops = [ops[i] for i in self.stored_idx]
        # compact32 (StreamConfig.acc_dtype int32/float32) stores 64-bit
        # accumulators in one 32-bit plane — but ONLY for leaves the
        # combiner numerically aggregates; pass-through fields (e.g. a
        # kept first-record value) stay exact, the opt-in covers
        # accumulator precision, not record contents. All-algebraic
        # compact storage unlocks the scatter-reduce fast path.
        wants32 = str(self.cfg.acc_dtype) in ("int32", "float32")
        self.compact32 = [
            wants32 and op in ("add", "min", "max") for op in self.stored_ops
        ]
        self.plane_dtypes = plane_dtypes(self.stored_kinds, self.compact32)
        self.fast_reduce = (
            wants32
            and all(op in ("add", "min", "max") for op in self.stored_ops)
            and len(self.plane_dtypes) == len(self.stored_idx)
        )
        n, k = self.ring.n_slots, self.local_key_capacity
        if n * k >= 2**31:
            raise ValueError(
                f"pane ring cells ({n} slots x {k} keys) exceed int32 "
                "addressing; lower key_capacity or window/pane ratio"
            )

    def _plane_identity(self, dtype: np.dtype, op: Optional[str]):
        """Identity element the plane is initialized/retargeted to (the
        scatter-reduce fast path merges straight into it)."""
        if op == "min":
            return (
                np.finfo(dtype).max
                if np.issubdtype(dtype, np.floating)
                else np.iinfo(dtype).max
            )
        if op == "max":
            return (
                np.finfo(dtype).min
                if np.issubdtype(dtype, np.floating)
                else np.iinfo(dtype).min
            )
        return 0

    def _plane_identities(self) -> List:
        if self.fast_reduce:
            return [
                self._plane_identity(dt, op)
                for dt, op in zip(self.plane_dtypes, self.stored_ops)
            ]
        return [0 for _ in self.plane_dtypes]

    def _combine_live(self, a_live: Tuple, b_live: Tuple) -> Tuple:
        """User combiner restricted to live leaves (dead inputs zero —
        sound because liveness closed over the combiner's dependence)."""
        arity = len(self.acc_kinds)
        shape = jnp.shape(a_live[0])

        def fill(live_vals):
            full = [None] * arity
            for pos, i in enumerate(self.live_idx):
                full[i] = live_vals[pos]
            for i in range(arity):
                if full[i] is None:
                    full[i] = jnp.zeros(
                        shape, dtype=self._acc_dtype(self.acc_kinds[i])
                    )
            return tuple(full)

        out = self.combine(fill(a_live), fill(b_live))
        return tuple(out[i] for i in self.live_idx)

    def _acc_dtype(self, kind: str):
        return np.int32 if kind == STR else NUMPY_DTYPES[kind]

    # -- SPMD hooks (shared ones live on BaseProgram; the combiner's
    # reconstructed key leaf and emissions use GLOBAL ids so the sharded
    # program matches the single-chip one) ------------------------------
    def _emission_keys(self):
        return self._global_key_ids(
            jnp.arange(self.local_key_capacity, dtype=jnp.int32)
        )

    def state_specs(self, state):
        """Sharding specs: planes/cnt are FLAT shard-major cell arrays
        (``[shard][slot][local_key]``) — splitting axis 0 contiguously
        hands each shard exactly its local ``[slots * local_keys]`` flat
        plane. Ring metadata and scalars replicate."""
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import AXIS

        specs = {
            k: jax.tree_util.tree_map(lambda _: P(), v)
            for k, v in state.items()
        }
        specs["planes"] = [P(AXIS) for _ in state["planes"]]
        specs["cnt"] = P(AXIS)
        return specs

    def rescale_key_leaf(self, arr, from_parallelism: int):
        """Checkpoint rescale for the FLAT word planes: the global flat
        layout is ``[shard][slot][local_key]`` (one contiguous
        ``[n_slots * k_local]`` block per shard), so the permutation
        routes through a canonical ``[slot][global_key]`` intermediate
        rather than the leading-key restack of the base layout."""
        S_o = max(1, from_parallelism)
        S_n = max(1, self.n_shards)
        if S_o == S_n:
            return arr
        n = self.ring.n_slots
        K = arr.shape[0] // n
        if K % S_o or K % S_n:
            raise ValueError(
                f"cannot rescale window state: key_capacity ({K}) must "
                f"divide evenly by both the snapshot parallelism ({S_o}) "
                f"and the target parallelism ({S_n})"
            )
        canon = arr.reshape(S_o, n, K // S_o).transpose(1, 2, 0).reshape(n, K)
        return np.ascontiguousarray(
            canon.reshape(n, K // S_n, S_n).transpose(2, 0, 1).reshape(-1)
        )

    def grow_key_leaf(self, old, new_init, shards: int = None):
        """Key-capacity growth for the FLAT word planes: per shard, each
        slot's old local-key run copies into the head of the slot's new
        (longer) run. ``shards`` overrides for process-local migration."""
        import numpy as np

        S = shards or max(1, self.n_shards)
        n = self.ring.n_slots
        k_lo = old.shape[0] // (S * n)
        out = np.array(new_init)
        k_ln = out.shape[0] // (S * n)
        k = min(k_lo, k_ln)
        out.reshape(S, n, k_ln)[:, :, :k] = old.reshape(S, n, k_lo)[:, :, :k]
        return out

    # ------------------------------------------------------------------
    def init_state(self):
        # planes live FLAT (cell = slot * keys + key): reshape wrappers
        # around the per-batch scatter defeat XLA's in-place aliasing and
        # re-copy the GB-scale state every step (4x step cost, measured);
        # flat layout also shards as contiguous per-device chunks
        n, kk = self.ring.n_slots, self.cfg.key_capacity
        hi0 = jnp.asarray(-1, dtype=jnp.int64)
        idents = self._plane_identities()
        return self._with_rules({
            "planes": [
                jnp.full((n * kk,), ident, dtype=dt)
                for dt, ident in zip(self.plane_dtypes, idents)
            ],
            "cnt": jnp.zeros((n * kk,), dtype=jnp.int32),
            "slot_pane": pane_ops.slot_targets(hi0, self.ring),
            "hi": hi0,
            "wm": jnp.asarray(W0, dtype=jnp.int64),
            "max_ts": jnp.asarray(W0, dtype=jnp.int64),
            "fired_through": jnp.asarray(W0, dtype=jnp.int64),
            "pending_fires": jnp.zeros((), dtype=jnp.int64),
            "evicted_unfired": jnp.zeros((), dtype=jnp.int64),
            "alert_overflow": jnp.zeros((), dtype=jnp.int64),
            "exchange_overflow": jnp.zeros((), dtype=jnp.int64),
            "window_fires": jnp.zeros((), dtype=jnp.int64),
            "late_dropped": jnp.zeros((), dtype=jnp.int64),
        })

    # ------------------------------------------------------------------
    # legacy typed-cell scatter — kept for SessionWindowProgram, which
    # stores typed [keys, slots] accumulators plus per-cell timestamps
    # ------------------------------------------------------------------
    def _scatter_cells(self, leaves, cnt, keys, batch_leaves, live, pane, combine):
        """Merge a batch into [K, N]-typed cell state via sort + segmented
        scan with ``combine`` (arrival order preserved); every state write
        happens at SEGMENT TAILS (unique indices)."""
        k, n = self.local_key_capacity, self.ring.n_slots
        slot = jnp.mod(pane, n)
        cell = keys.astype(jnp.int64) * n + slot
        perm, sc, sv, seg_starts = sort_by_key(cell, live, max_key=k * n)
        lifted_sorted = tuple(l[perm] for l in batch_leaves)
        prefix = segmented_scan(lifted_sorted, seg_starts, combine)
        tails = segment_tails(seg_starts) & sv

        b = sv.shape[0]
        pos = jnp.arange(b, dtype=jnp.int64)
        seg_first = jax.lax.associative_scan(
            jnp.maximum, jnp.where(seg_starts, pos, 0)
        )
        seg_count = (pos - seg_first + 1).astype(jnp.int32)

        flat_idx = jnp.where(tails, sc, k * n)
        sc_c = jnp.clip(sc, 0, k * n - 1)
        old_cnt_flat = cnt.reshape(-1)
        old_cnt = old_cnt_flat[sc_c]
        olds = tuple(a.reshape(-1)[sc_c] for a in leaves)
        merged = combine(olds, prefix)
        newvals = tuple(
            jnp.where((old_cnt > 0) & sv, m, p) for m, p in zip(merged, prefix)
        )
        new_leaves = [
            a.reshape(-1)
            .at[flat_idx]
            .set(v, mode="drop", unique_indices=True)
            .reshape(k, n)
            for a, v in zip(leaves, newvals)
        ]
        new_cnt = (
            old_cnt_flat.at[flat_idx]
            .add(jnp.where(tails, seg_count, 0), mode="drop", unique_indices=True)
            .reshape(k, n)
        )
        return new_leaves, new_cnt, sc, tails

    # ------------------------------------------------------------------
    # word-plane state merge (the hot path)
    # ------------------------------------------------------------------
    def _scatter_words(self, planes, cnt, keys, mid_cols, live, pane):
        """Merge a batch into the flat cell planes.

        Fast path (commutative combiner + 32-bit planes): one non-unique
        scatter-add/min/max per plane straight into the identity-
        initialized state — no sort, no segmented scan, no gathers.
        Generic path: sort by (slot, key), combine same-cell records
        with a segmented scan over LIVE leaves, then set-scatter merged
        storage words at segment tails (one 32-bit scatter per plane)."""
        k, n = self.local_key_capacity, self.ring.n_slots
        slot = jnp.mod(pane, n).astype(jnp.int32)
        cell = slot * k + keys  # slot-major == plane memory order

        if self.fast_reduce:
            idx = jnp.where(live, cell, n * k)
            lifted = self.lift(list(mid_cols))
            new_planes = []
            for s, (p, i, op) in enumerate(
                zip(planes, self.stored_idx, self.stored_ops)
            ):
                (val,) = pack_words(
                    [lifted[i]], [self.acc_kinds[i]], [self.compact32[s]]
                )
                new_planes.append(
                    getattr(p.at[idx], op)(val.astype(p.dtype), mode="drop")
                )
            new_cnt = cnt.at[idx].add(1, mode="drop")
            if self.allowed_lateness_ms > 0:
                touched_slot = (
                    jnp.zeros((n + 1,), dtype=jnp.int32)
                    .at[jnp.where(live, slot, n)]
                    .max(1, mode="drop")
                )[:n] > 0
            else:
                touched_slot = pane_ops.vary(
                    jnp.zeros((n,), dtype=bool), self.vary_axes
                )
            return new_planes, new_cnt, touched_slot

        perm, sc, sv, seg_starts = sort_by_key(cell, live, max_key=n * k)
        sc = sc.astype(jnp.int32)

        lifted = self.lift(list(mid_cols))
        live_sorted = tuple(lifted[i][perm] for i in self.live_idx)
        prefix = segmented_scan(live_sorted, seg_starts, self._combine_live)
        tails = segment_tails(seg_starts) & sv

        b = sv.shape[0]
        pos = jnp.arange(b, dtype=jnp.int64)
        seg_first = jax.lax.associative_scan(
            jnp.maximum, jnp.where(seg_starts, pos, 0)
        )
        seg_count = (pos - seg_first + 1).astype(jnp.int32)

        sc_c = jnp.clip(sc, 0, n * k - 1)
        old_words = [p[sc_c] for p in planes]
        old_cnt = cnt[sc_c]
        old_stored = unpack_words(old_words, self.stored_kinds, self.compact32)
        # live tuple for the OLD cell value: stored leaves from planes,
        # the key leaf reconstructed from the cell index
        old_live = self._live_from_stored(
            old_stored, self._global_key_ids(jnp.mod(sc_c, k))
        )
        merged = self._combine_live(tuple(old_live), prefix)
        has_old = (old_cnt > 0) & sv
        new_live = [
            jnp.where(has_old, m, p) for m, p in zip(merged, prefix)
        ]
        new_stored = [
            new_live[self.live_idx.index(i)] for i in self.stored_idx
        ]
        new_words = pack_words(new_stored, self.stored_kinds, self.compact32)

        flat_idx = jnp.where(tails, sc, n * k)
        new_planes = [
            p.at[flat_idx].set(
                w.astype(p.dtype), mode="drop", unique_indices=True
            )
            for p, w in zip(planes, new_words)
        ]
        new_cnt = cnt.at[flat_idx].set(
            old_cnt + jnp.where(tails, seg_count, 0),
            mode="drop",
            unique_indices=True,
        )
        if self.allowed_lateness_ms > 0:
            touched_slot = (
                jnp.zeros((n + 1,), dtype=jnp.int32)
                .at[jnp.where(tails, sc // k, n)]
                .max(1, mode="drop")
            )[:n] > 0
        else:
            touched_slot = pane_ops.vary(
                jnp.zeros((n,), dtype=bool), self.vary_axes
            )
        return new_planes, new_cnt, touched_slot

    def _live_from_stored(self, stored_vals: List, key_ids) -> List:
        """Assemble the live-leaf tuple from stored values + key ids."""
        out = []
        si = 0
        for i in self.live_idx:
            if i == self.key_leaf:
                kind = self.acc_kinds[i]
                if kind == STR:
                    out.append(key_ids.astype(jnp.int32))
                else:
                    out.append(key_ids.astype(NUMPY_DTYPES[kind]))
            else:
                out.append(stored_vals[si])
                si += 1
        return out

    # ------------------------------------------------------------------
    # dense fire path
    # ------------------------------------------------------------------
    def _fire_dense(
        self, planes, cnt, slot_pane, hi, wm_old, wm_new, fired_through, touched,
        emission_carry=None, budget_on=None, defer_on=None,
    ):
        """Fire due window ends from the ring.

        ``emission_carry`` (out_cols, count, ovf, fires) lets the jump
        sweep (:meth:`_sweep`) append fires across iterations into one
        emission buffer; None starts fresh. ``budget_on`` (traced bool)
        suspends the max_fires_per_step budget on non-final sweep
        iterations — a deferred fire there would fall out of ring
        coverage before the next drain tick could reach it.
        ``defer_on`` (traced, replicated bool) lets the pending ends
        whose alerts would overflow the ``alert_capacity`` buffer wait,
        in order, for the next step; only steps the executor drains
        after (no valid row) pass it. An end that alone overflows an
        empty buffer still fires and counts the excess. Not on a mesh
        with allowed lateness, whose per-shard refires would put the
        deferral's all-reduce under a per-shard branch."""
        ring = self.ring
        k, n, f = self.local_key_capacity, ring.n_slots, ring.n_fire_candidates
        cap = self.cfg.alert_capacity
        j = jnp.arange(f, dtype=jnp.int64)
        cand = hi - n + 1 + j
        ends = (cand + 1) * ring.pane_ms
        aligned = jnp.mod(ends, ring.slide_ms) == 0
        pending = aligned & (ends - 1 <= wm_new) & (ends - 1 > fired_through)
        budget = self.cfg.max_fires_per_step or f
        if budget_on is not None:
            budget = jnp.where(budget_on, budget, f)
        csum = jnp.cumsum(pending.astype(jnp.int32))
        fire_now = pending & (csum <= budget)
        can_defer = defer_on is not None and (
            self.n_shards == 1 or self.allowed_lateness_ms == 0
        )
        if self.allowed_lateness_ms > 0:
            # allowed-late arrivals re-fire already-fired windows they
            # touch (chapter3/README.md:212 option 2). Refires are EXEMPT
            # from the fire budget: the dirty/touched flag is per-step and
            # not persisted, so a deferred refire would be lost — and the
            # dirty set is per-shard anyway, while the budgeted pending
            # bookkeeping must stay replicated across shards.
            member = (slot_pane[:, None] <= cand[None, :]) & (
                slot_pane[:, None] > (cand[None, :] - ring.panes_per_window)
            )
            dirty = (touched.astype(jnp.int32) @ member.astype(jnp.int32)) > 0
            refire = (
                aligned
                & (ends - 1 <= fired_through)
                & (ends - 1 + self.allowed_lateness_ms > wm_old)
                & dirty
            )
            fire_now = fire_now | refire
        any_fire = jnp.any(fire_now)

        v = lambda x: pane_ops.vary(x, self.vary_axes)
        if emission_carry is None:
            emission_carry = self._zero_emission_carry()
        carry_out, carry_cnt, carry_ovf, carry_fires = emission_carry
        key_col = self._emission_keys()

        def do_fire(_):
            def cand_body(carry, jj):
                out_cols, count, ovf, fires, stop, n_done = carry

                def fire_one(c2):
                    out_cols, count, ovf, fires, stop, n_done = c2
                    e_pane = cand[jj]

                    def pane_body(c3, o):
                        has, acc_live = c3
                        pane_sel = e_pane - (ring.panes_per_window - 1) + o
                        slot_sel = jnp.mod(pane_sel, n).astype(jnp.int32)
                        row0 = slot_sel * k
                        rows = [
                            jax.lax.dynamic_slice(p, (row0,), (k,))
                            for p in planes
                        ]
                        cnt_row = jax.lax.dynamic_slice(cnt, (row0,), (k,))
                        ok = (slot_pane[slot_sel] == pane_sel) & (pane_sel >= 0)
                        present = ok & (cnt_row > 0)
                        stored = unpack_words(
                            rows, self.stored_kinds, self.compact32
                        )
                        cell_live = self._live_from_stored(stored, key_col)
                        merged = self._combine_live(
                            tuple(acc_live), tuple(cell_live)
                        )
                        new_acc = [
                            jnp.where(
                                present & has, m, jnp.where(present, c, a)
                            )
                            for m, c, a in zip(merged, cell_live, acc_live)
                        ]
                        return (has | present, new_acc), None

                    has0 = v(jnp.zeros((k,), dtype=bool))
                    acc0 = [
                        v(
                            jnp.zeros(
                                (k,), dtype=self._acc_dtype(self.acc_kinds[i])
                            )
                        )
                        for i in self.live_idx
                    ]
                    (has, acc_live), _ = jax.lax.scan(
                        pane_body,
                        (has0, acc0),
                        jnp.arange(ring.panes_per_window, dtype=jnp.int64),
                    )

                    # full accumulator (dead leaves zero), finalize + post
                    full = [None] * len(self.acc_kinds)
                    for posi, i in enumerate(self.live_idx):
                        full[i] = acc_live[posi]
                    for i, kd in enumerate(self.acc_kinds):
                        if full[i] is None:
                            full[i] = v(
                                jnp.zeros((k,), dtype=self._acc_dtype(kd))
                            )
                    results = jax.vmap(
                        lambda *leaves: tuple(self.finalize(tuple(leaves)))
                    )(*full)
                    post_cols, post_mask = self.post_chain.apply(
                        list(results), has
                    )
                    emit = post_mask & has
                    defer = jnp.asarray(False)
                    if can_defer:
                        n_emit = jnp.sum(emit, dtype=jnp.int32)
                        full = (count > 0) & (count + n_emit > cap)
                        defer = (
                            defer_on & pending[jj]
                            & (self._global_max(full) > 0)
                        )

                    # append-compact the fired alerts after current count
                    end_col = jnp.zeros((k,), dtype=jnp.int64) + ends[jj]
                    src_cols = post_cols + [key_col, end_col]
                    out_cols, new_count, overflowed = pane_ops.append_compact(
                        emit & ~defer, src_cols, out_cols, count, cap
                    )
                    # every (key, window) with content is one window fire,
                    # counted BEFORE the post-chain filter (metrics parity
                    # with Flink's per-trigger accounting)
                    return (
                        out_cols,
                        new_count,
                        ovf + overflowed,
                        fires + jnp.where(defer, 0, jnp.sum(has)).astype(
                            jnp.int64
                        ),
                        stop | defer,
                        n_done + (pending[jj] & ~defer).astype(jnp.int32),
                    )

                return jax.lax.cond(
                    fire_now[jj] & ~(stop & pending[jj]),
                    fire_one, lambda c2: c2,
                    (out_cols, count, ovf, fires, stop, n_done),
                ), None

            (out_cols, count, ovf, fires, _, n_done), _ = jax.lax.scan(
                cand_body,
                (
                    list(carry_out), carry_cnt, carry_ovf, carry_fires,
                    jnp.asarray(False), jnp.zeros((), dtype=jnp.int32),
                ),
                jnp.arange(f),
            )
            return out_cols, count, ovf, fires, n_done

        def no_fire(_):
            return (
                list(carry_out), carry_cnt, carry_ovf, carry_fires,
                jnp.zeros((), dtype=jnp.int32),
            )

        out_cols, count, overflow, n_fired, n_done = jax.lax.cond(
            any_fire, do_fire, no_fire, operand=None
        )
        # pending ends fire in order, so the fired ones are the first
        # n_done of them
        new_ft = jnp.maximum(
            fired_through,
            jnp.max(jnp.where(pending & (csum <= n_done), ends - 1, W0)),
        )
        n_deferred = (jnp.sum(pending) - n_done).astype(jnp.int64)
        # (cols, count, overflow, fires) is cumulative past the carry —
        # re-feed it as emission_carry to append further sweep fires
        return (out_cols, count, overflow, n_fired), new_ft, n_deferred

    def _sweep(
        self, planes, cnt, slot_pane, hi_target, ft0,
        wm_old, wm_new, keys, mid_cols, live, pane, init_leaves, drain,
    ):
        """Advance the ring from its current head to ``hi_target`` in
        safe chunks when one step spans more panes than the ring covers
        (a batch with a large event-time jump, or a stream gap).

        Each iteration (1) picks the largest head advance that neither
        evicts a slot with due-but-unfired windows nor strips coverage
        from a record not yet scattered, (2) retargets, (3) scatters the
        newly covered records, and (4) fires every end the watermark and
        the scatter frontier both allow (``wm_eff``): ends above the
        frontier could still receive contributions from records waiting
        in later chunks. Empty gaps are skipped in one hop (occupancy
        test), so the loop converges in ~panes_per_window/(N - P)
        iterations per occupied cluster — and in exactly ONE iteration
        whenever the fast-path predicate in ``_step`` would have held.

        Flink parity: a record-at-a-time runtime interleaves window
        fires with arrivals in exactly this order — each record lands
        before the watermark that its successors raise can fire its
        windows (reference chapter3/README.md:195-213)."""
        ring = self.ring
        n, kloc = ring.n_slots, self.local_key_capacity
        g, p_win = ring.pane_ms, ring.panes_per_window
        INF = jnp.int64(2**62)
        v = lambda x: pane_ops.vary(x, self.vary_axes)

        def gmin(x, mask):
            m = jnp.min(jnp.where(mask, x, INF))
            return -self._global_max(-m)

        def cond(c):
            return c[0] | (c[1] < hi_target)

        def body(c):
            (
                first, hi_cur, scattered_hi, planes, cnt, slot_pane,
                ft, evicted, emission, pending,
            ) = c
            occ = jnp.any(cnt.reshape(n, kloc) > 0, axis=1)
            unsafe = occ & ((slot_pane + p_win) * g - 1 > ft)
            unsafe_min = gmin(slot_pane, unsafe)
            unscat = live & (pane > scattered_hi)
            min_unscat = gmin(pane, unscat)
            hi_next = jnp.minimum(
                jnp.asarray(hi_target),
                jnp.minimum(unsafe_min + (n - 1), min_unscat + (n - p_win)),
            )
            hi_next = jnp.maximum(hi_next, hi_cur)

            with jax.named_scope("retarget"):
                planes2, cnt2, slot_pane2, ev = pane_ops.retarget_rows(
                    planes, cnt, slot_pane, hi_next, ft, ring, init_leaves,
                    self.vary_axes,
                )
            smask = unscat & (pane <= hi_next)
            with jax.named_scope("scatter"):
                planes2, cnt2, touched = self._scatter_words(
                    planes2, cnt2, keys, mid_cols, smask, pane
                )
            is_final = hi_next >= hi_target
            wm_eff = jnp.where(
                is_final, wm_new, jnp.minimum(wm_new, hi_next * g - 1)
            )
            with jax.named_scope("fire"):
                emission, ft2, pending = self._fire_dense(
                    planes2, cnt2, slot_pane2, hi_next, wm_old, wm_eff, ft,
                    touched, emission_carry=emission, budget_on=is_final,
                    defer_on=is_final & drain,
                )
            return (
                jnp.asarray(False), hi_next, hi_next, planes2, cnt2,
                slot_pane2, ft2, evicted + ev, emission, pending,
            )

        carry0 = (
            jnp.asarray(True),
            jnp.max(slot_pane),          # current head: top targeted pane
            -INF,                        # nothing scattered yet
            list(planes), cnt, slot_pane, ft0,
            v(jnp.zeros((), dtype=jnp.int64)),
            self._zero_emission_carry(),
            # pending derives from replicated fire scalars: unvarying
            jnp.zeros((), dtype=jnp.int64),
        )
        (
            _, _, _, planes, cnt, slot_pane, ft, evicted, emission, pending,
        ) = jax.lax.while_loop(cond, body, carry0)
        return planes, cnt, slot_pane, ft, evicted, emission, pending

    def _zero_emission_carry(self):
        cap = self.cfg.alert_capacity
        out_dtypes = [
            self._acc_dtype(kd) for kd in self.post_chain.out_kinds
        ] + [np.int32, np.int64]  # + key, window_end
        v = lambda x: pane_ops.vary(x, self.vary_axes)
        return (
            [v(jnp.zeros((cap,), dtype=dt)) for dt in out_dtypes],
            v(jnp.zeros((), dtype=jnp.int32)),
            v(jnp.zeros((), dtype=jnp.int64)),
            v(jnp.zeros((), dtype=jnp.int64)),
        )

    # ------------------------------------------------------------------
    def _step(self, state, cols, valid, ts, wm_lower):
        mid_cols, mask = self._apply_pre(cols, valid)
        ring = self.ring
        # fed no valid row: the executor drains after this step, so it
        # may defer ends that overflow the alert buffer
        drain = ~self._global_max(jnp.any(valid))

        wm_old = state["wm"]
        batch_max = self._global_max(jnp.max(jnp.where(mask, ts, W0)))
        new_max = jnp.maximum(state["max_ts"], batch_max)
        wm_new = jnp.maximum(
            wm_old, jnp.maximum(new_max - self.delay_ms, wm_lower)
        )

        # keyBy: route records to their key-owner shard (ICI all_to_all)
        mid_cols, mask, ts, xovf = self._exchange(mid_cols, mask, ts)
        mid_cols, key_col = self._split_key_col(mid_cols)
        keys = self._local_keys(key_col)

        late = pane_ops.late_mask(ts, wm_old, self.allowed_lateness_ms, ring) & mask
        live = mask & ~late

        pane = pane_ops.pane_of(ts, ring.pane_ms)
        batch_hi = self._global_max(jnp.max(jnp.where(live, pane, -1)))
        hi = jnp.maximum(state["hi"], batch_hi)

        init_leaves = [
            jnp.asarray(ident, dtype=p.dtype)
            for p, ident in zip(state["planes"], self._plane_identities())
        ]
        n_slots = ring.n_slots
        ft0 = state["fired_through"]

        # ---- fast path vs jump sweep ------------------------------------
        # The fast path (retarget -> scatter -> one fire pass) is only
        # sound when (a) retargeting to `hi` evicts no slot whose windows
        # still owe fires, and (b) every live record's pane fits the ring
        # at `hi` (pane > hi - N). A large event-time jump — one batch
        # spanning more panes than the ring, or a stream gap — breaks
        # both: due ends would be evicted unfired, and old/new panes
        # would alias the same slot mod N (observed as impossible window
        # sums). The sweep advances the ring in safe chunks instead.
        target_t = pane_ops.slot_targets(hi, ring)
        stale_t = state["slot_pane"] != target_t
        slot_last_end = (state["slot_pane"] + ring.panes_per_window) * ring.pane_ms
        # slot_pane < 0 marks virgin targets (hi starts at -1): they hold
        # nothing, so retargeting them is always safe — without this the
        # cold-start batch would detour through the sweep
        may_evict = self._global_max(
            jnp.max(
                jnp.where(
                    stale_t & (slot_last_end - 1 > ft0) & (state["slot_pane"] >= 0),
                    1,
                    0,
                )
            )
        ) > 0
        min_live_pane = -self._global_max(
            jnp.max(jnp.where(live, -pane, -(2**62)))
        )
        fast_ok = (~may_evict) & (min_live_pane > hi - n_slots)

        def fast_path(op):
            planes, cnt = op
            # named scopes label the device ops of each phase in the
            # compiled program's metadata (obs/compilation.py maps them);
            # while the newest pane holds, no slot is stale and the
            # retarget's row loop runs no iteration
            with jax.named_scope("retarget"):
                planes2, cnt2, slot_pane, evicted = pane_ops.retarget_rows(
                    planes, cnt, state["slot_pane"], hi, ft0, ring,
                    init_leaves, self.vary_axes,
                )
            with jax.named_scope("scatter"):
                planes2, cnt2, touched = self._scatter_words(
                    planes2, cnt2, keys, mid_cols, live, pane
                )
            with jax.named_scope("fire"):
                emission, new_ft, n_pending = self._fire_dense(
                    planes2, cnt2, slot_pane, hi, wm_old, wm_new, ft0,
                    touched, defer_on=drain,
                )
            return (
                planes2, cnt2, slot_pane, new_ft, evicted,
                emission, n_pending,
            )

        def sweep_path(op):
            planes, cnt = op
            with jax.named_scope("sweep"):
                return self._sweep(
                    planes, cnt, state["slot_pane"], hi, ft0,
                    wm_old, wm_new, keys, mid_cols, live, pane, init_leaves,
                    drain,
                )

        (
            planes, cnt, slot_pane, new_ft, evicted,
            (emit_cols, emit_count, overflow, n_fired), n_pending,
        ) = jax.lax.cond(
            fast_ok, fast_path, sweep_path,
            (list(state["planes"]), state["cnt"]),
        )
        # ends whose last pane fell below ring coverage can never fire
        # (or refire) again — advance fired_through past them so the
        # fast-path soundness predicate doesn't re-trip forever after a
        # sweep that ended on empty panes
        new_ft = jnp.maximum(
            new_ft,
            jnp.minimum(wm_new, (hi - n_slots + 1) * ring.pane_ms - 1),
        )
        emit_valid = (
            jnp.arange(self.cfg.alert_capacity, dtype=jnp.int32) < emit_count
        )

        n_shards = max(1, self.cfg.parallelism)
        key_out = emit_cols[-2]
        new_state = {
            "planes": planes,
            "cnt": cnt,
            "slot_pane": slot_pane,
            "hi": hi,
            "wm": wm_new,
            "max_ts": new_max,
            "fired_through": new_ft,
            # pending is computed from replicated scalars (hi/wm/ft), so
            # every shard holds the same value — pmax replicates it
            # without the n_shards inflation a psum would introduce
            "pending_fires": self._global_max(n_pending),
            "evicted_unfired": state["evicted_unfired"]
            + self._global_sum(evicted),
            "alert_overflow": state["alert_overflow"]
            + self._global_sum(overflow),
            "exchange_overflow": state.get(
                "exchange_overflow", jnp.zeros((), dtype=jnp.int64)
            )
            + self._global_sum(xovf),
            "window_fires": state["window_fires"] + self._global_sum(n_fired),
            # counted on-device so the job observes its drops even without
            # a late side output configured (0 when one is: delivered late
            # records are not drops)
            "late_dropped": state["late_dropped"]
            + (
                self._global_sum(jnp.sum(late).astype(jnp.int64))
                if self.count_late_as_dropped
                else 0
            ),
        }
        main = {
            "mask": emit_valid,
            "cols": tuple(emit_cols[:-2]),
            "subtask": key_out % n_shards,
            "window_end": emit_cols[-1],
        }
        if getattr(self, "emit_chain_key", False):
            # chained stages only (set by the executor before trace):
            # key + end give the chain glue a canonical cross-shard
            # order matching the single-chip fire order (end-major,
            # then key — see Runner._dispatch). Unchained jobs skip the
            # [alert_capacity] D2H fetch this would add per firing step.
            main["key"] = key_out
        emissions = {
            "main": main,
            "late": {"mask": late, "cols": tuple(mid_cols)},
        }
        return new_state, emissions
