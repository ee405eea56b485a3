"""Sharded (multi-chip) program variants: SPMD over a device mesh.

The single-chip programs become SPMD by overriding four hooks:
``_exchange`` (keyBy as ICI all_to_all), ``_local_keys`` (key -> owner's
dense slot), ``_global_max``/``_global_sum`` (watermark & counters via
all-reduces). Keyed state shards over the mesh axis: key ``k``
lives on shard ``k % S`` at local row ``k // S``. The whole step runs
under ``jax.shard_map`` so XLA schedules the collectives on ICI
(SURVEY.md §2.3: the TPU-native equivalent of Flink's keyed exchange).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.exchange import exchange_by_key, exchange_capacity
from ..parallel.mesh import AXIS, make_mesh
from .cep_program import CepProgram
from .count_program import (
    CountProcessProgram,
    CountWindowProgram,
    SlidingCountWindowProgram,
)
from .plan import JobPlan
from .process_program import ProcessWindowProgram
from .session_program import SessionProcessProgram, SessionWindowProgram
from .step import RollingProgram
from .window_program import WindowProgram


class _ShardedMixin:
    """Hook overrides shared by the sharded programs."""

    def _setup_sharding(self, cfg):
        s = cfg.parallelism
        if cfg.key_capacity % s:
            raise ValueError(
                f"key_capacity ({cfg.key_capacity}) must divide evenly by "
                f"parallelism ({s})"
            )
        if cfg.batch_size % s:
            raise ValueError(
                f"batch_size ({cfg.batch_size}) must divide evenly by "
                f"parallelism ({s})"
            )
        self.n_shards = s
        self.vary_axes = (AXIS,)
        self.local_key_capacity = cfg.key_capacity // s
        self.mesh = make_mesh(s)
        self.exchange_capacity = exchange_capacity(
            cfg.batch_size, s, cfg.exchange_capacity_factor
        )

    def _global_max(self, x):
        # The TPU compiler lowers only sum all-reduces of 64-bit values
        # (a pmax of the int64 watermark is UNIMPLEMENTED there), and an
        # all_gather's result stays per-shard to shard_map's type check:
        # gather every shard's value with a psum of one-hot rows, then
        # reduce locally. Exact for every dtype: other rows add zero.
        rows = jnp.arange(self.n_shards) == jax.lax.axis_index(AXIS)
        rows = rows.reshape((self.n_shards,) + (1,) * jnp.ndim(x))
        gathered = jax.lax.psum(jnp.where(rows, x, 0), AXIS)
        return jnp.max(gathered, axis=0).astype(x.dtype)

    def _global_sum(self, x):
        return jax.lax.psum(x, AXIS)

    def _exchange(self, mid_cols, mask, ts):
        keys = mid_cols[self.key_pos]
        cols, valid, ts2, ovf = exchange_by_key(
            list(mid_cols), mask, ts, keys, self.n_shards, self.exchange_capacity
        )
        return cols, valid, ts2, ovf

    def _local_keys(self, key_col):
        return (key_col.astype(jnp.int32)) // self.n_shards

    def _global_key_ids(self, local_ids):
        idx = jax.lax.axis_index(AXIS).astype(jnp.int32)
        return local_ids.astype(jnp.int32) * self.n_shards + idx

    def _row_offset(self, n_local_rows: int):
        return jax.lax.axis_index(AXIS).astype(jnp.int32) * n_local_rows

    def _sharded_jit(self):
        state = self.init_state()
        state_specs = self.state_specs(state)
        in_specs = (
            state_specs,
            P(AXIS),  # cols (tuple leaves share the spec via tree prefix)
            P(AXIS),  # valid
            P(AXIS),  # ts
            P(),      # wm_lower
        )
        # all emission leaves carry per-shard rows
        out_specs = (state_specs, P(AXIS))
        # traced_step(): the dynamic-rules wrapper when the plan declares
        # a RuleSet (rule leaves are 0-d -> P() above -> replicated, so
        # every shard evaluates the same rule version per batch), else
        # _step itself
        fn = jax.shard_map(
            self.traced_step(),
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
        )
        return jax.jit(fn, donate_argnums=0)


class ShardedWindowProgram(_ShardedMixin, WindowProgram):
    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        self._setup_sharding(cfg)

    def jitted_step(self):
        return self._sharded_jit()


class ShardedSessionWindowProgram(_ShardedMixin, SessionWindowProgram):
    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        self._setup_sharding(cfg)

    def jitted_step(self):
        return self._sharded_jit()


class ShardedSessionProcessProgram(_ShardedMixin, SessionProcessProgram):
    """Session windows + ProcessWindowFunction at parallelism N: the
    keyBy exchange routes records to their owner shard, element buffers
    and per-cell session metadata shard on the key axis, and the host
    callback maps shard-major state rows back to global key ids
    (closing round 2's last single-chip-only program shape)."""

    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        self._setup_sharding(cfg)

    def jitted_step(self):
        return self._sharded_jit()


class ShardedRollingProgram(_ShardedMixin, RollingProgram):
    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        self._setup_sharding(cfg)

    def jitted_step(self):
        return self._sharded_jit()


class ShardedCountWindowProgram(_ShardedMixin, CountWindowProgram):
    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        self._setup_sharding(cfg)

    def jitted_step(self):
        return self._sharded_jit()


class ShardedSlidingCountWindowProgram(_ShardedMixin, SlidingCountWindowProgram):
    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        self._setup_sharding(cfg)

    def jitted_step(self):
        return self._sharded_jit()


class ShardedCountProcessProgram(_ShardedMixin, CountProcessProgram):
    """Count-window process() at parallelism N: emission payloads carry
    GLOBAL key ids and per-shard element matrices, so the host callback
    needs no shard-aware row mapping."""

    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        self._setup_sharding(cfg)

    def jitted_step(self):
        return self._sharded_jit()


class ShardedCepProgram(_ShardedMixin, CepProgram):
    """CEP NFA matching at parallelism N: the keyBy exchange routes
    events to their key's owner shard, register/capture planes shard on
    the key axis, watermarks agree via pmax, and match/timeout records
    carry global key ids — the same advance loop runs unchanged per
    shard under shard_map."""

    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        self._setup_sharding(cfg)

    def jitted_step(self):
        return self._sharded_jit()


class ShardedProcessWindowProgram(_ShardedMixin, ProcessWindowProgram):
    """Full-window process() at parallelism N: the keyBy exchange routes
    records to their owner shard, element buffers shard on the key axis,
    and the host callback sees global key ids
    (reference chapter2/README.md:177-196 runs at parallelism N too)."""

    def __init__(self, plan: JobPlan, cfg):
        super().__init__(plan, cfg)
        self._setup_sharding(cfg)

    def jitted_step(self):
        return self._sharded_jit()
