"""The pane ring's retarget: a step that opens a pane clears only the
slot rows the advance makes stale, in place (``ops/panes.py``
``retarget_rows``).

The kernel is held to the whole-plane ``jnp.where`` it replaced, exactly,
on stale patterns from none to all slots. Then both places that advance
the ring run on the chapter-3 event-time job through ``execute``: the
fast path, on a stream whose event time moves a few panes a step with
out-of-order and late lines, and the jump sweep
(``WindowProgram._sweep``), on jumps of 61 and 20 minutes (732 and 240
panes, against a ring of 88 slots). The first jump lands between two
batches, with windows still due in the slots it would evict; the second
inside a batch that also holds lines from before it. The alert rows
(channel and window sum) must be exactly the benchmark's numpy
reference's (``benchmark/reference/``, which imports nothing of
tpustream), on one device and sharded over four.
"""

import collections
import datetime
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpustream import StreamExecutionEnvironment, TimeCharacteristic
from tpustream.config import StreamConfig
from tpustream.jobs.chapter3_bandwidth_eventtime import build
from tpustream.ops import panes as P
from tpustream.runtime.sources import ReplaySource

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH = 16
CHANNELS = 12
# the chapter-3 ring: 5 min windows of 5 s panes, 1 min out-of-orderness,
# so 60 + 12 panes and 16 of slack: 88 slots
RING = P.make_ring_spec(300_000, 5_000, 60_000, 0)


# -- the kernel against the whole-plane oracle --------------------------------

def whole_plane_retarget(planes, cnt, slot_pane, hi_pane, wm, spec, inits):
    """The plain oracle: the retarget as a whole-plane ``jnp.where``
    over each ``[n_slots, keys]`` view of the flat planes (every cell of
    every plane read and written)."""
    n = spec.n_slots
    target = P.slot_targets(hi_pane, spec)
    stale = slot_pane != target
    last_end = (slot_pane + spec.panes_per_window) * spec.pane_ms
    unfired = stale & (last_end - 1 > wm)
    rows = cnt.reshape(n, -1)
    evicted = jnp.sum(jnp.where(unfired, jnp.sum(rows, axis=1), 0))
    cnt = jnp.where(stale[:, None], 0, rows).reshape(-1)
    planes = [
        jnp.where(stale[:, None], init, p.reshape(n, -1)).reshape(-1)
        for p, init in zip(planes, inits)
    ]
    return planes, cnt, target, evicted


# H0 % 88 == 80, so a 20-pane advance wraps past slot 0
H0 = 88 * 11 + 80
FIRED_ALL = 2**62          # every window of every pane has fired
FIRED_NONE = -(2**62)      # no window has fired


@pytest.mark.parametrize("case", [
    # (slot_pane at, new hi, fired-through watermark)
    ("none", H0, H0, FIRED_NONE),
    ("one", H0, H0 + 1, FIRED_NONE),
    ("one_fired", H0, H0 + 1, FIRED_ALL),
    # a run of 20 stale slots that wraps mod n_slots; the watermark sits
    # inside the run, so some evicted rows had unfired windows and some not
    ("wrap", H0, H0 + 20, (H0 - 88 + 70) * 5_000),
    ("jump", H0, H0 + 10 * 88, FIRED_NONE),
    ("jump_fired", H0, H0 + 10 * 88, FIRED_ALL),
    # virgin slots (slot_pane < 0 at hi = -1): the first panes claim them
    ("virgin", -1, 5, FIRED_NONE),
    ("virgin_to_jump", -1, 3 * 88 + 7, FIRED_NONE),
], ids=lambda c: c[0])
def test_retarget_rows_matches_whole_plane_oracle(case):
    _, h_old, h_new, wm = case
    n, k = RING.n_slots, 48
    rng = np.random.default_rng(list(case[0].encode()))
    # two int32 words of an int64 sum, a min plane, and the counts
    planes = [jnp.asarray(rng.integers(-2**31, 2**31, n * k), jnp.int32)
              for _ in range(3)]
    inits = [jnp.int32(0), jnp.int32(0), jnp.int32(np.iinfo(np.int32).max)]
    cnt = jnp.asarray(rng.integers(0, 6, n * k), jnp.int32)
    slot_pane = P.slot_targets(jnp.int64(h_old), RING)
    args = (slot_pane, jnp.int64(h_new), jnp.int64(wm))

    want = whole_plane_retarget(planes, cnt, *args, RING, inits)
    got = jax.jit(
        lambda pl, c, sp, hi, w: P.retarget_rows(pl, c, sp, hi, w, RING,
                                                 inits)
    )(planes, cnt, *args)
    for g, w in zip(got[0], want[0]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    assert int(got[3]) == int(want[3])
    stale = int(np.sum(np.asarray(slot_pane) != np.asarray(want[2])))
    assert stale == {"none": 0, "one": 1, "one_fired": 1, "wrap": 20,
                     "virgin": 6}.get(case[0], n)
    if case[3] == FIRED_NONE:
        assert int(want[3]) > 0 or stale == 0
    if case[0] == "wrap":
        assert 0 < int(want[3]) < int(np.asarray(cnt).sum())


# -- the job, end to end -----------------------------------------------------


def _reference():
    spec = importlib.util.spec_from_file_location(
        "bandwidth_reference",
        os.path.join(REPO, "benchmark", "reference", "bandwidth_eventtime.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_stream(seed=31):
    """``(ts_s, channel, flow)`` in arrival order: three stretches of one
    line per channel every ~7 s of arrival time, event time trailing the
    arrival by 0-50 s (3% of lines by 61-450 s: late), the second
    stretch 61 minutes after the first and the third 20 minutes after
    the second. Flows are small, so every window alerts."""
    rng = np.random.default_rng(seed)
    out = []
    t0 = 1_566_957_600  # 2019-08-28T10:00:00+08:00
    for start, minutes in ((0, 6), (61 * 60 + 360, 4), (81 * 60 + 600, 2)):
        arrival = np.arange(start, start + minutes * 60, 7)
        for a in arrival:
            late = rng.random(CHANNELS) < 0.03
            trail = np.where(late, rng.integers(61, 451, CHANNELS),
                             rng.integers(0, 51, CHANNELS))
            for c in rng.permutation(CHANNELS):
                out.append((t0 + int(a) - int(trail[c]), int(c),
                            int(rng.integers(1_000, 10_000))))
    return out


def render(events):
    zone = datetime.timezone(datetime.timedelta(hours=8))
    return [
        f"{datetime.datetime.fromtimestamp(ts, zone):%Y-%m-%dT%H:%M:%S} "
        f"ch{c} {flow}"
        for ts, c, flow in events
    ]


def run_job(lines, parallelism, acc_dtype):
    env = StreamExecutionEnvironment(StreamConfig(
        batch_size=BATCH, key_capacity=16, parallelism=parallelism,
        acc_dtype=acc_dtype))
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    h = build(env, env.add_source(ReplaySource(lines))).collect()
    env.execute(f"retarget-p{parallelism}")
    # the window sum behind each Mbps (the map's float64 rounding aside)
    rows = [(t.f0, round(t.f1 * 60 * 1024 * 1024 / 8)) for t in h.items]
    return rows, env.metrics.summary()


@pytest.fixture(scope="module")
def stream():
    events = make_stream()
    ts_ms = np.asarray([e[0] for e in events], np.int64) * 1000
    key = np.asarray([e[1] for e in events], np.int64)
    flow = np.asarray([e[2] for e in events], np.int64)
    step_ends = np.minimum(
        np.arange(BATCH, len(events) + BATCH, BATCH), len(events))
    ref = _reference()
    want = ref.alerts(ts_ms, key, flow, step_ends)
    rows = collections.Counter(
        (f"ch{k}", int(s)) for k, s in zip(want["key"], want["sum"]))
    return {"lines": render(events), "ts_ms": ts_ms, "step_ends": step_ends,
            "rows": rows, "late": want["late"]}


def test_stream_takes_both_ring_paths(stream):
    """Most steps advance the ring a few panes (the fast path); two
    advance it past the whole ring, one of them in a batch that spans
    more panes than the ring holds (only the sweep can take those): the
    traffic reaches both places the ring advances."""
    pane = stream["ts_ms"] // RING.pane_ms
    starts = np.concatenate([[0], stream["step_ends"][:-1]])
    spans = np.asarray([pane[a:b].max() - pane[a:b].min()
                        for a, b in zip(starts, stream["step_ends"])])
    hi = np.maximum.accumulate(np.maximum.reduceat(pane, starts))
    advances = np.diff(hi)
    assert (advances >= RING.n_slots).sum() == 2
    assert (spans >= RING.n_slots).sum() == 1
    assert ((advances > 0) & (advances < RING.n_slots)).sum() > 50
    assert stream["late"] > 0
    assert sum(stream["rows"].values()) > 1_000


# the job's defaults keep each window sum as two int32 words merged by
# a sort and set-scatter (a cell's count gates its stale words); int32
# pane sums take the scatter-add fast path, which merges straight into
# the plane, so there a stale row must read its identity
@pytest.mark.parametrize("acc_dtype", ["float64", "int32"])
@pytest.mark.parametrize("parallelism", [1, 4])
def test_rows_match_the_reference_across_jumps_and_late_lines(
    stream, parallelism, acc_dtype
):
    got, m = run_job(stream["lines"], parallelism, acc_dtype)
    assert collections.Counter(got) == stream["rows"]
    assert m["late_dropped"] == stream["late"]
    assert m["evicted_unfired"] == 0
