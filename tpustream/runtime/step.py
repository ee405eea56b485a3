"""Compiled device programs: one jitted XLA step per job.

Phase B of every job (reference chapter1/README.md:57-61) compiles here
into a single ``(state, batch) -> (state, emissions)`` function — the
TPU-native replacement for Flink's thread-per-operator runtime. State is
donated to the jit so keyed HBM arrays update in place.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..api.functions import as_callable
from ..config import StreamConfig
from ..records import BOOL, F64, I64, NUMPY_DTYPES, STR
from ..ops import rolling as rolling_ops
from .device import DeviceChain, unwrap_record, wrap_record
from .plan import JobPlan

LONG_MIN = -(2**63)

# dynamic-rule leaves ride the state pytree under these keys (only when
# the plan declares a RuleSet — rule-less jobs keep their exact treedef)
RULES_KEY = "__rules__"
RULE_VERSION_KEY = "__rule_version__"


def _np_dtype(kind: str):
    return NUMPY_DTYPES[kind]


class Emissions:
    """Host-side view of one step's outputs (already numpy)."""

    def __init__(self, streams: Dict[str, dict]):
        self.streams = streams


class BaseProgram:
    """Common structure: pre chain -> stateful core -> post chain."""

    def __init__(self, plan: JobPlan, cfg: StreamConfig):
        self.plan = plan
        self.cfg = cfg
        # the pre chain (user device ops before the stateful op) wraps
        # the VISIBLE record: a computed-KeySelector job's synthetic
        # trailing key column must never reach user filters, so the
        # chain is built without it and _apply_pre routes it around
        in_kinds, in_tables = plan.record_kinds, plan.tables
        if plan.synthetic_key and in_kinds:
            in_kinds, in_tables = in_kinds[:-1], in_tables[:-1]
        # dynamic rules (tpustream/broadcast): RuleParams in user fns
        # resolve to host values here at chain-build time (DeviceChain's
        # concrete output dry-run) and to the traced state leaves inside
        # _rules_step — one mechanism covers pre/post chains and CEP
        # predicates without any per-program plumbing
        self.ruleset = plan.rules
        self.pre_chain = DeviceChain(plan.device_pre, in_kinds, in_tables)
        self.mid_kinds = self.pre_chain.out_kinds
        self.mid_tables = self.pre_chain.out_tables
        # post chain input kinds are set by the subclass (stateful output)
        self.post_chain: Optional[DeviceChain] = None

    def _apply_pre(self, cols, valid):
        """Run the pre chain over the visible record columns; the
        synthetic derived-key column (if any) bypasses user ops and
        reattaches as the trailing column for the exchange."""
        if self.plan.synthetic_key:
            out, mask = self.pre_chain.apply(list(cols[:-1]), valid)
            return list(out) + [cols[-1]], mask
        return self.pre_chain.apply(cols, valid)

    def _split_key_col(self, mid_cols):
        """(visible mid cols, raw key column). Call AFTER the exchange
        (the synthetic column must ride the all_to_all with its
        records); everything downstream of this sees only the visible
        record."""
        if self.plan.synthetic_key:
            return list(mid_cols[:-1]), mid_cols[-1]
        return list(mid_cols), mid_cols[self.key_pos]

    def _key_table(self):
        """Intern table for key ids (host fire evaluation). For a
        computed KeySelector this is the DerivedKeyTable, whose lookup
        returns the original derived value."""
        if self.plan.synthetic_key:
            return self.plan.tables[-1]
        return self.mid_tables[self.key_pos]

    # subclasses: init_state(), _step(state, cols, valid, ts, wm_lower)

    def _with_rules(self, state: dict) -> dict:
        """Attach the rule pytree to a family's init state: one 0-d
        leaf per rule plus the applied-update counter. Replicated on
        the mesh (P() specs), so every shard applies version N at the
        same batch boundary."""
        if self.ruleset is None:
            return state
        state = dict(state)
        state[RULES_KEY] = self.ruleset.device_leaves()
        state[RULE_VERSION_KEY] = jnp.asarray(self.ruleset.version, jnp.int64)
        return state

    def _rules_step(self, state, cols, valid, ts, wm_lower):
        """The traced wrapper when rules are declared: strip the rule
        leaves, bind them for the duration of the _step trace (every
        RuleParam then resolves to its leaf — parameters compile as
        DATA), and pass them through unchanged. Updates happen host-side
        between steps as plain buffer swaps on ``state[RULES_KEY]``, so
        the compiled program never changes."""
        rules = state[RULES_KEY]
        inner = {
            k: v for k, v in state.items()
            if k not in (RULES_KEY, RULE_VERSION_KEY)
        }
        with self.ruleset.bound(rules):
            new_state, emissions = self._step(inner, cols, valid, ts, wm_lower)
        new_state = dict(new_state)
        new_state[RULES_KEY] = rules
        new_state[RULE_VERSION_KEY] = state[RULE_VERSION_KEY]
        return new_state, emissions

    def traced_step(self):
        """What jit (and the sharded mixin's shard_map) compile."""
        return self._step if self.ruleset is None else self._rules_step

    def jitted_step(self):
        return jax.jit(self.traced_step(), donate_argnums=0)

    def _replicate_rule_specs(self, specs: dict) -> dict:
        """Force P() on the rule subtree. Rule leaves are replicated by
        contract — and in tenant mode they are [T] vectors indexed by
        tenant slot, which a shape-based ndim rule (RollingProgram's
        ``ndim >= 1``) would wrongly shard over the key axis."""
        from jax.sharding import PartitionSpec as P

        if RULES_KEY in specs:
            specs = dict(specs)
            specs[RULES_KEY] = jax.tree_util.tree_map(
                lambda _: P(), specs[RULES_KEY]
            )
            specs[RULE_VERSION_KEY] = P()
        return specs

    def state_specs(self, state):
        """Mesh sharding specs for the state pytree (default: arrays with
        a leading key axis of ndim >= 2 shard on it, scalars replicate).
        Programs with other layouts override."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import AXIS

        return self._replicate_rule_specs(jax.tree_util.tree_map(
            lambda leaf: P(AXIS) if leaf.ndim >= 2 else P(), state
        ))

    def rescale_key_leaf(self, arr: np.ndarray, from_parallelism: int):
        """Re-lay a key-sharded state leaf saved at a different
        parallelism onto THIS program's shard-major layout (checkpoint
        rescale — Flink savepoints restore at any parallelism).

        Default layout: leading key axis stacked shard-major, row
        ``shard * k_local + local`` holding global key
        ``local * S + shard``. The global shape is parallelism-
        independent, so rescale is a pure row permutation through the
        canonical key-major order. WindowProgram overrides for its flat
        word-plane layout."""
        S_o = max(1, from_parallelism)
        S_n = max(1, self.n_shards)
        if S_o == S_n:
            return arr
        K = arr.shape[0]
        if K % S_o or K % S_n:
            raise ValueError(
                f"cannot rescale keyed state: key_capacity ({K}) must "
                f"divide evenly by both the snapshot parallelism ({S_o}) "
                f"and the target parallelism ({S_n})"
            )
        rest = tuple(range(2, arr.ndim + 1))
        canon = arr.reshape(S_o, K // S_o, *arr.shape[1:]).transpose(
            1, 0, *rest
        ).reshape(arr.shape)
        return np.ascontiguousarray(
            canon.reshape(K // S_n, S_n, *arr.shape[1:]).transpose(
                1, 0, *rest
            ).reshape(arr.shape)
        )

    def grow_key_leaf(
        self, old: np.ndarray, new_init: np.ndarray, shards: int = None
    ) -> np.ndarray:
        """Migrate a key-sharded leaf into THIS (larger-capacity)
        program's layout (dynamic key-capacity growth). The shard count
        is unchanged and interned key ids are stable, so key ``g`` stays
        on shard ``g % S`` at the same local row — each shard's old rows
        copy into the head of its new block; new rows keep the fresh
        init values (identities / unseen sentinels). ``shards``
        overrides the shard count for PROCESS-LOCAL migration (the
        arrays then cover only this process's contiguous shard blocks —
        the copy is shard-local either way)."""
        S = shards or max(1, self.n_shards)
        k_lo = old.shape[0] // S
        out = np.array(new_init)
        k_ln = out.shape[0] // S
        k = min(k_lo, k_ln)  # k_ln < k_lo only when re-laying fresh state
        out.reshape(S, k_ln, *old.shape[1:])[:, :k] = old.reshape(
            S, k_lo, *old.shape[1:]
        )[:, :k]
        return out

    # short operator label for the obs layer: every runner's metric
    # series carry {operator: <this>} (de-aliased per chain stage), the
    # Flink-metric-group analogue of the operator name. Subclasses
    # override with their operator kind.
    operator_name = "operator"

    # device-carried scalar state worth exposing as gauges: the event
    # clock ("wm" — the authoritative max_seen - delay watermark),
    # newest seen timestamp, pane-ring head, and deferred fire backlog.
    # Fetched ONCE per job at finalize/snapshot time, never on the
    # per-step path.
    OBS_STATE_SCALARS = ("wm", "max_ts", "hi", "pending_fires")

    def obs_state_scalars(self, state) -> dict:
        """The subset of OBS_STATE_SCALARS present in ``state`` as 0-d
        leaves (still on device — the caller device_gets them)."""
        if not isinstance(state, dict):
            return {}
        return {
            n: state[n]
            for n in self.OBS_STATE_SCALARS
            if n in state and getattr(state[n], "ndim", None) == 0
        }

    # state-dict keys grouped into named memory components for the
    # obs/memory.py HBM accounting (component -> tuple of state keys);
    # each program family claims its big array leaves, everything
    # unclaimed (counters, clocks) reports under "scalars"
    STATE_COMPONENT_KEYS: dict = {}

    def state_components(self) -> dict:
        """Flat ``state key -> component name`` map derived from
        :data:`STATE_COMPONENT_KEYS`."""
        out = {}
        for comp, keys in self.STATE_COMPONENT_KEYS.items():
            for k in keys:
                out[k] = comp
        return out

    # False for programs with no time semantics (per-record rolling,
    # count windows, stateless chains): a clock tick / EOS flush step can
    # never produce output for them, so the executor skips it
    fires_on_clock = True

    # True for programs whose emission payload is gathered from live
    # device state AFTER the step (full-window process()): the executor
    # must dispatch them before enqueuing another step, so emission
    # pipelining (StreamConfig.async_depth) is forced off
    emissions_reference_state = False

    # True when the "main" emission's valid rows are a compacted PREFIX
    # of the buffer (window/session append-compaction): the executor can
    # then fetch only ~count rows instead of the full alert_capacity
    # buffer — on a thin host link that is the difference between
    # kilobytes and megabytes per firing step
    main_emission_prefix = False

    # record fields that cross the wire and come back as their int64
    # image (records.f64_image) instead of as float64: the rolling
    # program's order-only float64 fields; no other program has any
    image_fields: Tuple[int, ...] = ()

    # -- SPMD hooks: identity on one chip, mesh collectives when sharded --
    n_shards = 1
    vary_axes: tuple = ()

    # host-side fetch of state/emission leaves for host-evaluated
    # programs: plain numpy on one host; the multi-host executor swaps
    # in a local-shard fetcher (each process evaluates ITS keys' fires)
    _host_fetch = staticmethod(np.asarray)

    def _host_shard_base(self) -> int:
        """First mesh-shard index covered by this process's local state
        rows (0 on one host)."""
        import jax as _jax

        if _jax.process_count() <= 1:
            return 0
        return _jax.process_index() * (self.n_shards // _jax.process_count())

    def _row_offset(self, n_local_rows: int):
        """Offset of this shard's emission rows in the concatenated
        output (0 on one chip; shard_index * local_rows on a mesh) so
        host-side ``order`` indices address the stacked arrays."""
        return jnp.zeros((), dtype=jnp.int32)

    def _global_max(self, x):
        return x

    def _global_sum(self, x):
        return x

    def _exchange(self, mid_cols, mask, ts):
        return mid_cols, mask, ts, jnp.zeros((), dtype=jnp.int64)

    def _local_keys(self, key_col):
        return key_col.astype(jnp.int32)

    def _global_key_ids(self, local_ids):
        """Local state row -> global key id (identity on one chip; the
        sharded mixin interleaves by shard)."""
        return local_ids.astype(jnp.int32)


class StatelessProgram(BaseProgram):
    """map/filter-only pipeline (reference chapter1 job, SURVEY.md §3.1).

    Emissions are compacted on device into a prefix buffer so the host
    fetches ~alert-count rows, not the whole batch — for a sparse filter
    like the >90 threshold that is a ~100x cut in D2H bytes."""

    fires_on_clock = False
    main_emission_prefix = True
    operator_name = "stateless"

    def __init__(self, plan: JobPlan, cfg: StreamConfig):
        super().__init__(plan, cfg)
        self.out_kinds = self.mid_kinds
        self.out_tables = self.mid_tables
        # never lossy: a filterless pipeline emits the full batch
        self.emit_capacity = max(cfg.alert_capacity, cfg.batch_size)

    def init_state(self):
        return self._with_rules(
            {"alert_overflow": jnp.zeros((), dtype=jnp.int64)}
        )

    def _step(self, state, cols, valid, ts, wm_lower):
        from ..ops import panes as pane_ops

        out_cols, mask = self.pre_chain.apply(cols, valid)
        _, emit_valid, overflow, gathered = pane_ops.compact(
            mask, list(out_cols), self.emit_capacity
        )
        return (
            {"alert_overflow": state["alert_overflow"] + overflow},
            {"main": {"mask": emit_valid, "cols": tuple(gathered)}},
        )


class RollingProgram(BaseProgram):
    """keyBy + rolling aggregate, emitting per record
    (reference chapter2/.../ComputeCpuMax.java:26)."""

    fires_on_clock = False
    operator_name = "rolling"
    STATE_COMPONENT_KEYS = {"rolling_planes": rolling_ops.ROLLING_STATE_KEYS}

    def __init__(self, plan: JobPlan, cfg: StreamConfig):
        super().__init__(plan, cfg)
        st = plan.stateful
        self.key_pos = plan.key_pos
        if st.kind == "rolling":
            self.combine = rolling_ops.make_combiner(st.rolling_kind, st.rolling_pos)
        else:  # rolling_reduce with a user function
            fn = as_callable(st.rolling_fn, "reduce")
            kinds, tables = self.mid_kinds, self.mid_tables

            def combine(a, b):
                ra = wrap_record(kinds, tables, list(a))
                rb = wrap_record(kinds, tables, list(b))
                out, _, _ = unwrap_record(fn(ra, rb))
                return tuple(out)

            self.combine = combine
        self.post_chain = DeviceChain(
            plan.device_post, self.mid_kinds, self.mid_tables
        )
        self.out_kinds = self.post_chain.out_kinds
        self.out_tables = self.post_chain.out_tables
        self.image_fields = self._order_only_f64()
        # the kinds the device holds: an order-only float64 field rides
        # as its int64 image (records.f64_image), in two int32 planes
        self.state_kinds = [
            I64 if i in self.image_fields else k
            for i, k in enumerate(self.mid_kinds)
        ]

    def _order_only_f64(self) -> Tuple[int, ...]:
        """Record fields (input = emitted positions) whose float64 the
        step only compares, selects or passes through: every field of
        ``max``/``min``/``max_by``/``min_by`` and the keep-first fields
        of ``sum``, when no device op reads the record before or after
        the aggregate. They cross the wire, live in state and come back
        as their exact int64 image, compared in Java's order; the TPU
        emulates float64, and its answers are not always among its
        inputs. A field the 32-bit accumulator opt-in narrows keeps its
        float32 plane, and the key column keeps its own path."""
        st, plan = self.plan.stateful, self.plan
        if st.kind != "rolling" or plan.device_pre or plan.device_post:
            return ()
        c32 = self._compact32
        c32 = c32 if isinstance(c32, list) else [c32] * len(self.mid_kinds)
        return tuple(
            i for i, k in enumerate(self.mid_kinds)
            if k == F64 and not c32[i]
            and (plan.synthetic_key or i != self.key_pos)
            and not (st.rolling_kind == "sum" and i == st.rolling_pos)
        )

    @property
    def _compact32(self):
        """Per-leaf 32-bit accumulator flags: the lossy opt-in
        (acc_dtype int32/float32) applies ONLY to the field the rolling
        aggregate actually combines numerically — pass-through record
        fields (Flink's kept first-record values, chapter2/README.md:
        60-66) and whole-record max_by/min_by winners stay exact."""
        if str(self.cfg.acc_dtype) not in ("int32", "float32"):
            return False
        st = self.plan.stateful
        if st.kind == "rolling" and st.rolling_kind in ("max", "min", "sum"):
            return [i == st.rolling_pos for i in range(len(self.mid_kinds))]
        return False

    @property
    def _sentinel_leaf(self):
        """Keep-first STR leaf whose plane doubles as occupancy for the
        commutative fast path (interned ids >= 0; -1 marks unseen) —
        saves the dedicated seen-plane gather on every batch."""
        st = self.plan.stateful
        if st.kind != "rolling" or st.rolling_kind not in ("max", "min", "sum"):
            return None
        for i, kd in enumerate(self.mid_kinds):
            if kd == STR and i != st.rolling_pos and i != self.key_pos:
                return i
        return None

    def init_state(self):
        return self._with_rules(
            rolling_ops.init_rolling_state(
                self.cfg.key_capacity, self.state_kinds, self._compact32,
                sentinel_leaf=self._sentinel_leaf,
            )
        )

    def legacy_init_state(self):
        """The init state of a build before the images (checkpoint
        format v12: a float64 plane per order-only field)."""
        return self._with_rules(
            rolling_ops.init_rolling_state(
                self.cfg.key_capacity, self.mid_kinds, self._compact32,
                sentinel_leaf=self._sentinel_leaf,
            )
        )

    def upgrade_legacy_state(self, state: dict) -> dict:
        """A v12 state (numpy leaves, :meth:`legacy_init_state`'s tree)
        in this program's layout: each image field's float64 plane
        becomes the two int32 planes of its image, bit for bit."""
        from ..ops.wordplanes import leaf_plane_slices, pack_words
        from ..records import f64_image

        c32 = self._compact32
        old = leaf_plane_slices(self.mid_kinds, c32)
        planes = []
        for i, sl in enumerate(old):
            leaf = list(state["planes"][sl])
            if i in self.image_fields:
                leaf = pack_words([f64_image(leaf[0])], [I64])
            planes.extend(np.asarray(p) for p in leaf)
        return dict(state, planes=planes)

    def state_specs(self, state):
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import AXIS

        # rolling state: seen [K], storage planes [K] -> sharded on axis 0
        return self._replicate_rule_specs(jax.tree_util.tree_map(
            lambda leaf: P(AXIS) if leaf.ndim >= 1 else P(), state
        ))

    def _step(self, state, cols, valid, ts, wm_lower):
        mid_cols, mask = self._apply_pre(cols, valid)
        mid_cols, mask, ts, _ = self._exchange(mid_cols, mask, ts)
        mid_cols, gkeys = self._split_key_col(mid_cols)
        keys = self._local_keys(gkeys)
        st = self.plan.stateful
        fast_kwargs = {}
        if st.kind == "rolling":
            fast_kwargs = dict(
                rolling_kind=st.rolling_kind, rolling_pos=st.rolling_pos,
                sentinel_leaf=self._sentinel_leaf,
            )
            key_kind = (
                None
                if self.plan.synthetic_key  # key not in the visible record
                else self.mid_kinds[self.key_pos]
            )
            if self.key_pos != st.rolling_pos and key_kind in (STR, I64):
                # key column is key-invariant: emit it straight from the
                # sorted key ids and never touch its state plane
                dt = jnp.int32 if key_kind == STR else jnp.int64
                fast_kwargs["key_col"] = self.key_pos
                fast_kwargs["key_emit"] = (
                    lambda sks: self._global_key_ids(sks).astype(dt)
                )
        emit_ts = getattr(self, "emit_ts", False)
        if emit_ts:
            # chained stages with event-time windows downstream: the
            # rolling aggregate forwards the input record's timestamp,
            # permuted by the step's own sort (no extra inversion)
            fast_kwargs["sort_also"] = (ts,)
        out = rolling_ops.rolling_step(
            state, keys, tuple(mid_cols), mask, self.combine,
            self.state_kinds, self._compact32, **fast_kwargs,
        )
        new_state, emitted_sorted, sv, sk, inv = out[:5]
        # emissions stay in sorted order; the host un-permutes via
        # emissions["order"] (device-side inverse gathers dominate the
        # rolling step cost on v5e)
        out_cols, out_mask = self.post_chain.apply(list(emitted_sorted), sv)
        n_shards = max(1, self.cfg.parallelism)
        # subtask from the sorted RAW key (aggregation-invariant), mapped
        # back to the global id space
        subtask = self._global_key_ids(
            jnp.where(sv, sk, 0).astype(jnp.int32)
        ) % n_shards
        main = {
            "mask": out_mask,
            "cols": tuple(out_cols),
            "subtask": subtask,
            "order": self._row_offset(inv.shape[0]) + inv.astype(jnp.int32),
        }
        if emit_ts:
            main["ts"] = out[5][0]
        return new_state, {"main": main}


def build_program(plan: JobPlan, cfg: StreamConfig) -> BaseProgram:
    sharded = cfg.parallelism > 1
    if plan.stateful is None:
        return StatelessProgram(plan, cfg)
    if plan.stateful.kind in ("rolling", "rolling_reduce"):
        if sharded:
            from .sharded import ShardedRollingProgram

            return ShardedRollingProgram(plan, cfg)
        return RollingProgram(plan, cfg)
    if plan.stateful.kind == "window":
        if plan.stateful.window is not None and plan.stateful.window.kind == "count":
            spec = plan.stateful.window
            sliding = spec.count_slide and spec.count_slide != spec.count
            if plan.stateful.apply_kind == "process":
                if sharded:
                    from .sharded import ShardedCountProcessProgram

                    return ShardedCountProcessProgram(plan, cfg)
                from .count_program import CountProcessProgram

                return CountProcessProgram(plan, cfg)
            if sliding:
                if sharded:
                    from .sharded import ShardedSlidingCountWindowProgram

                    return ShardedSlidingCountWindowProgram(plan, cfg)
                from .count_program import SlidingCountWindowProgram

                return SlidingCountWindowProgram(plan, cfg)
            if sharded:
                from .sharded import ShardedCountWindowProgram

                return ShardedCountWindowProgram(plan, cfg)
            from .count_program import CountWindowProgram

            return CountWindowProgram(plan, cfg)
        if plan.stateful.window is not None and plan.stateful.window.kind == "session":
            if plan.stateful.apply_kind == "process":
                if sharded:
                    from .sharded import ShardedSessionProcessProgram

                    return ShardedSessionProcessProgram(plan, cfg)
                from .session_program import SessionProcessProgram

                return SessionProcessProgram(plan, cfg)
            if sharded:
                from .sharded import ShardedSessionWindowProgram

                return ShardedSessionWindowProgram(plan, cfg)
            from .session_program import SessionWindowProgram

            return SessionWindowProgram(plan, cfg)
        if plan.stateful.apply_kind == "process":
            if sharded:
                from .sharded import ShardedProcessWindowProgram

                return ShardedProcessWindowProgram(plan, cfg)
            from .process_program import ProcessWindowProgram

            return ProcessWindowProgram(plan, cfg)
        if sharded:
            from .sharded import ShardedWindowProgram

            return ShardedWindowProgram(plan, cfg)
        from .window_program import WindowProgram

        return WindowProgram(plan, cfg)
    if plan.stateful.kind == "cep":
        if sharded:
            from .sharded import ShardedCepProgram

            return ShardedCepProgram(plan, cfg)
        from .cep_program import CepProgram

        return CepProgram(plan, cfg)
    raise NotImplementedError(plan.stateful.kind)
