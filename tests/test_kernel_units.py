"""Per-kernel unit tests (SURVEY.md §4: "unit tests per kernel — parse,
keyed max, pane assignment, watermark monotonicity per the spec at
chapter3/README.md:380-396").

Each test checks a device kernel against a plain-Python record-at-a-time
reference implementation on randomized inputs.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from tpustream.ops import panes as P
from tpustream.ops import sessions as S
from tpustream.ops.rolling import init_rolling_state, make_combiner, rolling_step
from tpustream.ops.segments import (
    segment_tails,
    segmented_scan,
    sort_by_key,
)


# ---------------------------------------------------------------- panes ----

def test_ring_spec_covers_window_plus_horizon():
    spec = P.make_ring_spec(
        size_ms=300_000, slide_ms=5_000, delay_ms=60_000, allowed_lateness_ms=0
    )
    assert spec.pane_ms == 5_000
    assert spec.panes_per_window == 60
    # ring must hold the window plus the out-of-orderness horizon
    assert spec.n_slots >= 60 + 12
    assert spec.n_fire_candidates == spec.n_slots + 60


def test_pane_assignment_and_last_window_end():
    spec = P.make_ring_spec(60_000, 15_000, 0, 0)  # 1-min window, 15-s slide
    ts = jnp.asarray([0, 14_999, 15_000, 59_999, 60_000], dtype=jnp.int64)
    assert list(np.asarray(P.pane_of(ts, spec.pane_ms))) == [0, 0, 1, 3, 4]
    # last window containing ts is [e-size, e) with the largest aligned e > ts
    ends = np.asarray(P.last_window_end(ts, spec))
    for t, e in zip(np.asarray(ts), ends):
        assert e % 15_000 == 0
        assert e - 60_000 <= t < e, (t, e)
        # e is maximal: the next slide's window would start after ts
        assert e + 15_000 - 60_000 > t


def test_late_mask_matches_flink_contract():
    spec = P.make_ring_spec(60_000, 60_000, 0, 0)  # tumbling 1 min
    # record at t=30s belongs to window [0,60s) which fires once wm >= 59999
    ts = jnp.asarray([30_000], dtype=jnp.int64)
    assert not bool(P.late_mask(ts, jnp.int64(59_998), 0, spec)[0])
    assert bool(P.late_mask(ts, jnp.int64(59_999), 0, spec)[0])
    # allowed lateness extends the live horizon
    assert not bool(P.late_mask(ts, jnp.int64(59_999), 10_000, spec)[0])
    assert bool(P.late_mask(ts, jnp.int64(69_999), 10_000, spec)[0])


def test_fire_candidates_fire_exactly_once_per_boundary():
    spec = P.make_ring_spec(300_000, 5_000, 60_000, 0)
    hi = jnp.int64(500)  # newest pane seen: stream has reached ~2_500_000 ms
    # wm trails hi by the 60s delay (the realistic operating point; panes
    # further back have rotated out of the ring and are no longer candidates)
    fired_ends = []
    wm_lo = jnp.int64(2_400_000)
    for wm_hi in range(2_400_000, 2_500_000, 7_000):  # advance in odd steps
        _, ends, fire = P.fire_candidates(hi, wm_lo, jnp.int64(wm_hi), spec)
        fired_ends.extend(np.asarray(ends)[np.asarray(fire)].tolist())
        wm_lo = jnp.int64(wm_hi)
        last = wm_hi
    # every fired end is slide-aligned, fired exactly once, and the set is
    # exactly the slide boundaries e with e-1 in (2_400_000, last]
    assert len(fired_ends) == len(set(fired_ends))
    expect = [
        e for e in range(0, 3_000_000, 5_000) if 2_400_000 < e - 1 <= last
    ]
    assert sorted(fired_ends) == expect


def test_retarget_clears_stale_slots_and_counts_unfired():
    spec = P.make_ring_spec(10_000, 10_000, 0, 0, slack=2)
    n = spec.n_slots
    cnt = jnp.ones((n,), dtype=jnp.int32)  # one record in every slot row
    acc = [jnp.full((n,), 7, dtype=jnp.int32)]
    init = [jnp.zeros((), dtype=jnp.int32)]
    slot_pane = P.slot_targets(jnp.int64(n - 1), spec)  # ring at panes [0, n)
    # jump far ahead: every slot becomes stale
    hi = jnp.int64(10 * n)
    wm = jnp.int64(0)  # nothing has fired
    acc2, cnt2, tgt, evicted = P.retarget_rows(
        acc, cnt, slot_pane, hi, wm, spec, init)
    assert int(evicted) == n  # all n records were evicted before firing
    assert int(np.asarray(cnt2).sum()) == 0
    assert int(np.asarray(acc2[0]).sum()) == 0
    # same jump but wm already past every stale window end: nothing "unfired"
    wm_done = jnp.int64((n + spec.panes_per_window) * spec.pane_ms)
    _, _, _, evicted2 = P.retarget_rows(
        acc, cnt, slot_pane, hi, wm_done, spec, init)
    assert int(evicted2) == 0


def test_compact_matches_numpy_and_counts_overflow():
    rng = np.random.default_rng(3)
    mask = rng.random(4096) < 0.3
    vals = rng.integers(0, 1000, 4096)
    capacity = 256
    idx, valid, overflow, (out,) = P.compact(
        jnp.asarray(mask), [jnp.asarray(vals)], capacity
    )
    want = vals[mask]
    got = np.asarray(out)[np.asarray(valid)]
    assert list(got) == list(want[:capacity])
    assert int(overflow) == max(0, mask.sum() - capacity)


# ------------------------------------------------------------- segments ----

def test_segmented_scan_matches_python_reference():
    rng = np.random.default_rng(0)
    n, k = 512, 13
    keys = rng.integers(0, k, n).astype(np.int32)
    vals = rng.random(n).astype(np.float32)
    valid = rng.random(n) < 0.9

    perm, sk, sv, seg_starts = sort_by_key(
        jnp.asarray(keys), jnp.asarray(valid), max_key=k
    )
    scanned = segmented_scan(
        (jnp.asarray(vals)[perm],), seg_starts, lambda a, b: (a[0] + b[0],)
    )[0]

    # reference: per-key running sum in arrival order
    run = {}
    want = np.zeros(n, dtype=np.float64)
    for i in range(n):
        if valid[i]:
            run[keys[i]] = run.get(keys[i], 0.0) + vals[i]
            want[i] = run[keys[i]]
    inv = np.empty(n, dtype=np.int64)
    inv[np.asarray(perm)] = np.arange(n)
    got = np.asarray(scanned)[inv]
    np.testing.assert_allclose(got[valid], want[valid], rtol=1e-5)

    # tails: exactly one per present key among valid rows
    tails = np.asarray(segment_tails(seg_starts) & sv)
    tail_keys = np.asarray(sk)[tails]
    assert sorted(tail_keys.tolist()) == sorted(set(keys[valid].tolist()))


# -------------------------------------------------------------- rolling ----

def _rolling_reference(kind, pos, batches, n_cols):
    """Record-at-a-time Flink-semantics reference (chapter2/README.md:52-66)."""
    state = {}
    out = []
    for keys, cols, valid in batches:
        emis = [np.zeros(len(keys), dtype=np.float64) for _ in range(n_cols)]
        for i in range(len(keys)):
            if not valid[i]:
                continue
            rec = tuple(c[i] for c in cols)
            k = keys[i]
            if k not in state:
                state[k] = rec
            else:
                cur = list(state[k])
                if kind == "max":
                    cur[pos] = max(cur[pos], rec[pos])
                elif kind == "min":
                    cur[pos] = min(cur[pos], rec[pos])
                elif kind == "sum":
                    cur[pos] = cur[pos] + rec[pos]
                elif kind == "max_by":
                    if rec[pos] > cur[pos]:
                        cur = list(rec)
                elif kind == "min_by":
                    if rec[pos] < cur[pos]:
                        cur = list(rec)
                state[k] = tuple(cur)
            for c in range(n_cols):
                emis[c][i] = state[k][c]
        out.append(emis)
    return out


@pytest.mark.parametrize("kind", ["max", "min", "sum", "max_by", "min_by"])
def test_rolling_matches_reference_across_batches(kind):
    rng = np.random.default_rng(42)
    kcap, b, nb = 17, 128, 3
    combine = make_combiner(kind, 1)
    state = init_rolling_state(kcap, ["str", "f64"])

    batches = []
    for _ in range(nb):
        keys = rng.integers(0, kcap, b).astype(np.int32)
        c0 = rng.integers(0, 100, b).astype(np.int32)
        c1 = np.round(rng.random(b) * 100, 1).astype(np.float64)
        valid = rng.random(b) < 0.85
        batches.append((keys, (c0, c1), valid))

    want = _rolling_reference(kind, 1, batches, 2)
    for (keys, cols, valid), w in zip(batches, want):
        state, emis_sorted, sv, sk, inv = rolling_step(
            state,
            jnp.asarray(keys),
            tuple(jnp.asarray(c) for c in cols),
            jnp.asarray(valid),
            combine,
            ["str", "f64"],
        )
        inv = np.asarray(inv)
        for c in range(2):
            arrival = np.asarray(emis_sorted[c])[inv]
            np.testing.assert_allclose(
                arrival[valid], w[c][valid], rtol=1e-5
            )


# pairwise cover of (kind x key_col x pos x compact) instead of the
# full 24-point product: the axes select independent code paths
# (combiner intrinsic / key-emission fast path / i64-two-plane vs f64
# leaf / 32-bit layout), so every pair of settings appears at least
# once while the suite runs 9 points, not 24 (gate budget, r4 next #7)
@pytest.mark.parametrize(
    "kind,key_col,pos,compact_mode",
    [
        ("max", None, 2, "none"),
        ("min", None, 2, "none"),
        ("sum", None, 2, "none"),
        ("max", 0, 1, "none"),
        ("sum", 0, 2, "none"),
        ("min", 0, 1, "none"),
        ("max", None, 1, "agg"),
        ("sum", 0, 1, "agg"),
        ("min", None, 2, "agg"),
    ],
)
def test_rolling_commutative_fast_path_matches_oracle(
    kind, key_col, pos, compact_mode
):
    """The max/min/sum fast path (single-column scan, key-column
    reconstruction, cond-deferred new-key bookkeeping) must match the
    record-at-a-time oracle batch by batch — including batches with no
    new keys at all, which exercise the steady-state cond branch.
    ``pos=1`` aggregates an i64 leaf, covering the two-word-plane
    lo/hi pack-and-scatter of the aggregated column; ``compact_mode
    "agg"`` covers its single-plane 32-bit layout."""
    rng = np.random.default_rng(7)
    kinds = ["str", "i64", "f64", "bool"]
    kcap, b = 13, 96
    compact = (
        False if compact_mode == "none" else [i == pos for i in range(4)]
    )
    combine = make_combiner(kind, pos)
    state = init_rolling_state(kcap, kinds, compact)

    batches = []
    for it in range(5):
        # confine early batches to few keys so later batches are all-seen
        hi = kcap if it < 2 else 4
        keys = rng.integers(0, hi, b).astype(np.int32)
        c0 = keys.copy()
        c1 = rng.integers(-50, 50, b).astype(np.int64)
        c2 = np.round(rng.random(b) * 100, 1).astype(np.float64)
        c3 = rng.random(b) < 0.5
        valid = rng.random(b) < 0.9
        batches.append((keys, (c0, c1, c2, c3), valid))

    want = _rolling_reference(kind, pos, batches, 4)
    kw = {}
    if key_col is not None:
        kw = dict(key_col=0, key_emit=lambda s: s.astype(jnp.int32))
    for (keys, cols, valid), w in zip(batches, want):
        state, emis_sorted, sv, sk, inv = rolling_step(
            state,
            jnp.asarray(keys),
            tuple(jnp.asarray(c) for c in cols),
            jnp.asarray(valid),
            combine,
            kinds,
            compact,
            rolling_kind=kind,
            rolling_pos=pos,
            **kw,
        )
        inv = np.asarray(inv)
        for c in range(4):
            arrival = np.asarray(emis_sorted[c])[inv]
            np.testing.assert_allclose(
                arrival[valid].astype(np.float64),
                w[c][valid].astype(np.float64),
                rtol=1e-5,
            )


@pytest.mark.parametrize("kind", ["max", "min", "sum"])
def test_rolling_fast_path_sentinel_occupancy_matches_oracle(kind):
    """sentinel_leaf derives `seen` from a keep-first STR plane
    initialized to -1 (interned ids are >= 0) — must be exact through
    new-key and steady-state batches, with the seen plane untouched."""
    rng = np.random.default_rng(11)
    kinds = ["str", "str", "f64"]
    kcap, b, pos = 13, 96, 2
    combine = make_combiner(kind, pos)
    state = init_rolling_state(kcap, kinds, sentinel_leaf=1)

    batches = []
    for it in range(5):
        hi = kcap if it < 2 else 4
        keys = rng.integers(0, hi, b).astype(np.int32)
        c0 = keys.copy()
        c1 = rng.integers(0, 50, b).astype(np.int32)  # interned ids >= 0
        c2 = np.round(rng.random(b) * 100, 1).astype(np.float64)
        valid = rng.random(b) < 0.9
        batches.append((keys, (c0, c1, c2), valid))

    want = _rolling_reference(kind, pos, batches, 3)
    for (keys, cols, valid), w in zip(batches, want):
        state, emis_sorted, sv, sk, inv = rolling_step(
            state,
            jnp.asarray(keys),
            tuple(jnp.asarray(c) for c in cols),
            jnp.asarray(valid),
            combine,
            kinds,
            rolling_kind=kind,
            rolling_pos=pos,
            key_col=0,
            key_emit=lambda s: s.astype(jnp.int32),
            sentinel_leaf=1,
        )
        inv = np.asarray(inv)
        for c in range(3):
            arrival = np.asarray(emis_sorted[c])[inv]
            np.testing.assert_allclose(
                arrival[valid].astype(np.float64),
                w[c][valid].astype(np.float64),
                rtol=1e-6,
            )
    # the dedicated seen plane stays cold on the sentinel path
    assert not np.asarray(state["seen"]).any()


# ------------------------------------------------------------- sessions ----

def test_session_runs_link_and_fire_propagation():
    gap = 10_000
    # panes of exactly `gap`; occupancy pattern: [A A gap B] for one key
    occ = jnp.asarray([[True, True, False, True]])
    mn = jnp.asarray([[1_000, 10_500, S.TS_MAX, 32_000]], dtype=jnp.int64)
    mx = jnp.asarray([[2_000, 11_000, S.W0, 33_000]], dtype=jnp.int64)
    link, run_end = S.session_runs(occ, mn, mx, gap)
    # pane1 joins pane0 (10_500 - 2_000 < gap); pane3 starts a new run
    assert np.asarray(link).tolist() == [[False, True, False, False]]
    assert np.asarray(run_end).tolist() == [[False, True, False, True]]
    # firing run-ends propagates to every member of the run
    fire_end = np.asarray(run_end) & np.array([[False, True, False, False]])
    fired = S.propagate_to_run(jnp.asarray(fire_end), link)
    assert np.asarray(fired).tolist() == [[True, True, False, False]]


def test_session_runs_do_not_link_across_wide_gap():
    gap = 10_000
    occ = jnp.asarray([[True, True]])
    mn = jnp.asarray([[0, 19_500]], dtype=jnp.int64)
    mx = jnp.asarray([[500, 19_900]], dtype=jnp.int64)
    link, _ = S.session_runs(occ, mn, mx, gap)
    # adjacent panes but 19_500 - 500 >= gap: separate sessions
    assert np.asarray(link).tolist() == [[False, False]]


# ------------------------------------------------------------ watermark ----

def test_watermark_monotone_under_decreasing_timestamps():
    """The BoundedOutOfOrderness contract (chapter3/README.md:380-396):
    wm = max_seen_ts - delay and never retreats, exercised through the
    flagship compiled step with batches whose max ts DECREASES."""
    import __graft_entry__ as ge

    program, _ = ge._build_flagship(1, 64, 32)
    state = program.init_state()
    wms = []
    base = 1_566_957_600_000
    for step, hi_ms in enumerate([600_000, 300_000, 100_000, 700_000]):
        ts = jnp.asarray(
            base + np.linspace(0, hi_ms, 64).astype(np.int64), jnp.int64
        )
        cols = (
            ts // 1000,
            jnp.zeros(64, jnp.int32),
            jnp.full((64,), 100, jnp.int64),
        )
        state, _ = program._step(
            state, cols, jnp.ones(64, bool), ts, jnp.asarray(P.W0, jnp.int64)
        )
        wms.append(int(np.asarray(state["wm"])))
    assert wms == sorted(wms), "watermark retreated"
    # and it equals max_seen - delay (1 min) once data pushes it forward
    assert wms[-1] == base + 700_000 - 60_000
