"""Pane-ring window state: the TPU-native window machinery.

A sliding window of (size, slide) is decomposed into panes of
``g = gcd(size, slide)`` ms (SURVEY.md §5 "pane-sharded reduction").
Per-record work is O(1): scatter into a dense ``[keys, n_slots]``
accumulator ring indexed by ``pane_id % n_slots``. A window FIRE composes
its ``P = size//g`` panes; fire candidates are enumerated statically
(ring slots plus P trailing window ends) so the whole thing stays inside
one jitted program with static shapes.

This replaces Flink's per-record assignment of sliding-window elements to
all 60 overlapping windows (reference
chapter3/.../BandwidthMonitorWithEventTime.java:46, hot loop in
SURVEY.md §3.4) with one scatter + an amortized ring composition.

Watermark semantics follow the monotone ``max_seen - delay`` contract of
BoundedOutOfOrdernessTimestampExtractor (chapter3/README.md:380-396);
window end ``e`` fires when the watermark first reaches ``e - 1``
(Flink's ``window.maxTimestamp() <= watermark``), and an element is late
when its LAST window has fired past allowed lateness
(chapter3/README.md:209-213).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

W0 = -(2**62)  # "long min" safe against offset arithmetic

# state-dict keys of the pane-ring layout (accumulator planes, per-cell
# element counts, the slot->pane mapping), for the obs/memory.py
# component accounting
PANE_RING_STATE_KEYS = ("planes", "cnt", "slot_pane")


class RingSpec(NamedTuple):
    pane_ms: int          # pane granularity g
    panes_per_window: int  # P
    slide_ms: int
    size_ms: int
    n_slots: int          # N  (>= P + lateness horizon + slack)
    n_fire_candidates: int  # N + P

    @property
    def lateness_horizon_panes(self) -> int:
        return self.n_slots - self.panes_per_window


def make_ring_spec(
    size_ms: int,
    slide_ms: int,
    delay_ms: int,
    allowed_lateness_ms: int,
    slack: int = 16,
) -> RingSpec:
    import math

    g = math.gcd(size_ms, slide_ms)
    p = size_ms // g
    horizon = -(-(delay_ms + allowed_lateness_ms) // g)  # ceil
    n = p + horizon + slack
    return RingSpec(g, p, slide_ms, size_ms, n, n + p)


def pane_of(ts: jnp.ndarray, g: int) -> jnp.ndarray:
    return jnp.floor_divide(ts, g)


def last_window_end(ts: jnp.ndarray, spec: RingSpec) -> jnp.ndarray:
    """End of the LAST window containing ts: the largest multiple of slide
    that is <= ts + size (window [e-size, e) with e > ts)."""
    return jnp.floor_divide(ts + spec.size_ms, spec.slide_ms) * spec.slide_ms


def late_mask(ts, wm, allowed_lateness_ms: int, spec: RingSpec):
    """True where the record is late beyond allowed lateness: all its
    windows have fired and purged."""
    return last_window_end(ts, spec) - 1 + allowed_lateness_ms <= wm


def slot_targets(hi_pane, spec: RingSpec):
    """For each ring slot s, the unique pane id in (hi-N, hi] congruent to
    s mod N. Slots for panes the stream hasn't reached stay empty."""
    n = spec.n_slots
    s = jnp.arange(n, dtype=jnp.int64)
    return hi_pane - jnp.mod(hi_pane - s, n)


def retarget(acc_leaves, cnt, slot_pane, hi_pane, wm, spec: RingSpec, init_leaves):
    """Advance the ring to cover (hi-N, hi]: slots whose stored pane no
    longer matches their target are cleared (evicted).

    Returns (acc_leaves, cnt, new_slot_pane, evicted_unfired_records) —
    the count covers records in evicted panes whose last window had NOT
    fired yet (a ring-undersized condition; n_slots must cover
    (size + delay + lateness)/pane plus slack).
    """
    target = slot_targets(hi_pane, spec)
    stale = slot_pane != target
    last_end = (slot_pane + spec.panes_per_window) * spec.pane_ms
    unfired = stale & (last_end - 1 > wm)
    evicted = jnp.sum(jnp.where(unfired, jnp.sum(cnt, axis=0), 0))
    cnt = jnp.where(stale[None, :], 0, cnt)
    acc_leaves = [
        jnp.where(stale[None, :], init, a)
        for a, init in zip(acc_leaves, init_leaves)
    ]
    return acc_leaves, cnt, target, evicted


def retarget_rows(plane_leaves, cnt, slot_pane, hi_pane, wm, spec: RingSpec, init_leaves):
    """:func:`retarget` for slot-major ``[n_slots, keys]`` state planes
    (the word-plane window layout): slots are ROWS, so stale slots clear
    whole rows and the unfired count sums each stale row."""
    target = slot_targets(hi_pane, spec)
    stale = slot_pane != target
    last_end = (slot_pane + spec.panes_per_window) * spec.pane_ms
    unfired = stale & (last_end - 1 > wm)
    evicted = jnp.sum(jnp.where(unfired, jnp.sum(cnt, axis=1), 0))
    cnt = jnp.where(stale[:, None], 0, cnt)
    plane_leaves = [
        jnp.where(stale[:, None], init, p)
        for p, init in zip(plane_leaves, init_leaves)
    ]
    return plane_leaves, cnt, target, evicted


def fire_candidates(hi_pane, wm_old, wm_new, spec: RingSpec):
    """Static set of window-end candidates and which of them fire now.

    Candidates are windows whose LAST pane lies in (hi-N, hi+P]: every
    window that can still contain ring data, including the P "trailing"
    windows that slide past the newest pane (they fire at end-of-stream /
    clock advance). Returns (cand_last_pane [F], ends [F], fire [F]).
    """
    f = spec.n_fire_candidates
    j = jnp.arange(f, dtype=jnp.int64)
    cand = hi_pane - spec.n_slots + 1 + j
    ends = (cand + 1) * spec.pane_ms
    aligned = jnp.mod(ends, spec.slide_ms) == 0
    fire = aligned & (ends - 1 <= wm_new) & (ends - 1 > wm_old)
    return cand, ends, fire


def vary(x, axes):
    """Mark a freshly-created constant as device-varying over ``axes`` so
    VMA tracking under shard_map accepts it alongside sharded data."""
    if not axes:
        return x
    return jax.lax.pcast(x, axes, to="varying")


def compact(mask_flat: jnp.ndarray, cols, capacity: int):
    """Device-side compaction: first `capacity` set rows of mask.

    Returns (indices [A], valid [A], overflow, gathered cols [A]).

    Implemented as an int32 cumsum + position scatter of the row index,
    then small gathers. The two obvious alternatives both fail on v5e:
    ``jnp.nonzero``/``searchsorted`` run their prefix machinery in
    emulated int64 (pair-of-u32 reduce-windows that exceed scoped vmem
    at ~1e6 masks — verified compile failure), while scattering every
    column directly pays the full-length scatter once per column instead
    of once total.
    """
    idx, added = compact_positions(mask_flat, capacity)
    count = added
    out_cols = [x[idx] for x in cols]
    valid = jnp.arange(capacity, dtype=jnp.int32) < count
    overflow = jnp.maximum(count - capacity, 0).astype(jnp.int64)
    return idx, valid, overflow, out_cols


def compact_positions(mask_flat: jnp.ndarray, capacity: int, base: int = 0):
    """The shared compaction core: scatter each set row's SOURCE index to
    its output position ``base + rank``. Returns (idx [capacity], count)
    where ``count`` is the total set rows (may exceed capacity)."""
    c = jnp.cumsum(mask_flat.astype(jnp.int32))
    count = c[-1]
    n = mask_flat.shape[0]
    pos = jnp.where(mask_flat, base + c - 1, capacity)  # past-cap rows drop
    src = jnp.arange(n, dtype=jnp.int32)
    idx = (
        jnp.zeros((capacity,), dtype=jnp.int32)
        .at[pos]
        .set(src, mode="drop", unique_indices=True)
    )
    return idx, count


def append_compact(mask_flat, src_cols, out_cols, count, capacity):
    """Append the set rows of ``mask_flat`` after ``count`` existing rows
    of the fixed ``[capacity]`` output columns. Returns
    (out_cols, new_count, overflowed)."""
    idx, added = compact_positions(mask_flat, capacity, base=count)
    new_count = jnp.minimum(count + added, capacity)
    ar = jnp.arange(capacity, dtype=jnp.int32)
    in_new = (ar >= count) & (ar < new_count)
    out_cols = [
        jnp.where(in_new, s[idx].astype(o.dtype), o)
        for o, s in zip(out_cols, src_cols)
    ]
    overflowed = jnp.maximum(count + added - capacity, 0).astype(jnp.int64)
    return out_cols, new_count, overflowed
