"""A step's fetch launches its device slice programs before the next
step's dispatch.

The chip runs programs in launch order, so a count-sized slice of step
k launched after step k+1's dispatch would wait for all of step k+1.
The executor plans the fetch of the steps in flight just before each
dispatch (``Runner._prepare_fetch``): these tests spy on the step calls
and on ``Runner._slice_stream``, and check that every slice of step k
comes before step k+1's dispatch on the prefix path (event-time
windows) and on the device-compaction path (a filtered rolling sum, with
steps that spill past ``compaction_capacity``), that the counter
``fetch_slices_behind_step_total`` stays 0, and that the sink rows,
order included, are those of the synchronous run.
"""

import collections
import functools

import jax
import numpy as np
import pytest

from tpustream import StreamExecutionEnvironment, TimeCharacteristic, Tuple2
from tpustream.config import ObsConfig, StreamConfig
from tpustream.runtime.executor import Runner, _Inflight
from tpustream.runtime.sources import ReplaySource


def window_lines(n_batches=24, per_batch=16):
    """Event time advances 10 s a batch and every line is a new channel,
    so each step fires about two 5 s slides, over a growing number of
    channels: the fire counts cross several pow2 fetch buckets."""
    t0 = np.datetime64("2019-08-28T09:00:00")
    out = []
    for b in range(n_batches):
        for i in range(per_batch):
            ts = t0 + np.timedelta64(10 * b + i % 10, "s")
            out.append(f"{ts} c{b * per_batch + i} {100 + 7 * i}")
    return out


def run_window(lines, **over):
    from tpustream.jobs.chapter3_bandwidth_eventtime import build

    cfg = StreamConfig(batch_size=16, key_capacity=512, alert_capacity=2048,
                       obs=ObsConfig(enabled=True), **over)
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    rows = []
    build(env, env.add_source(ReplaySource(lines))).add_sink(
        lambda t: rows.append((t.f0, t.f1))
    )
    return rows, env.execute("fetch-order-window")


def rolling_lines(n_batches=20, per_batch=64):
    """Batch b has (13 b) % 65 rows that pass the filter: counts from 0
    to 64 against a compaction capacity of 32, so some steps fetch
    compacted rows (in two pow2 buckets) and others spill."""
    out = []
    for b in range(n_batches):
        passing = (13 * b) % (per_batch + 1)
        for i in range(per_batch):
            v = 100 + i if i < passing else -1
            out.append(f"1 k{(b + i) % 5} {v}")
    return out


def run_rolling(lines, **over):
    def parse(line):
        items = line.split(" ")
        return Tuple2(items[1], int(items[2]))

    cfg = StreamConfig(batch_size=64, compaction_capacity=32,
                       obs=ObsConfig(enabled=True), **over)
    env = StreamExecutionEnvironment(cfg)
    rows = []
    (
        env.add_source(ReplaySource(lines))
        .map(parse)
        .filter(lambda t: t.f1 > 0)
        .key_by(0)
        .sum(1)
        .add_sink(lambda t: rows.append((t.f0, t.f1)))
    )
    return rows, env.execute("fetch-order-rolling")


JOBS = {
    "prefix": (run_window, window_lines),
    "compaction": (run_rolling, rolling_lines),
}

@functools.lru_cache(maxsize=None)
def sync_rows(job):
    """The job's rows at async_depth 1, computed once per job."""
    run, lines = JOBS[job]
    return tuple(run(lines(), async_depth=1)[0])


def series_total(res, suffix):
    series = res.metrics.obs_snapshot()["metrics"]["series"]
    return sum(s["value"] for s in series if s["name"].endswith(suffix))


class Spy:
    """Records each step dispatch and each fetch slice, with the step
    whose output the slice reads (by the identity of its leaves). With
    ``wait`` each dispatch waits for its step to finish, as a host
    slower than the device finds it at the next dispatch."""

    def __init__(self, monkeypatch, wait=True):
        self.dispatched = 0
        self.owner = {}  # id(output leaf) -> step index (0-based)
        self.keep = []   # the outputs, so ids are not reused
        self.slices = []  # (step index, steps dispatched, rows, leaves)
        counted = Runner._counted_step
        slice_stream = Runner._slice_stream
        spy = self

        def counted_step(runner, inner):
            step = counted(runner, inner)

            def dispatch(*args, **kw):
                out = step(*args, **kw)
                if wait:
                    jax.block_until_ready(out)
                _state, em, _counts, compact = out
                k = spy.dispatched
                spy.dispatched += 1
                for leaf in jax.tree_util.tree_leaves((em, compact)):
                    spy.owner[id(leaf)] = k
                spy.keep.append((em, compact))
                return out

            return dispatch

        def sliced(stream, b, cap):
            steps = {
                spy.owner[id(leaf)]
                for leaf in jax.tree_util.tree_leaves(stream)
                if id(leaf) in spy.owner
            }
            assert len(steps) == 1, steps
            n = sum(
                1 for a in jax.tree_util.tree_leaves(stream)
                if getattr(a, "ndim", 0) >= 1 and a.shape[0] == cap
            )
            spy.slices.append((steps.pop(), spy.dispatched, b, n))
            return slice_stream(stream, b, cap)

        monkeypatch.setattr(Runner, "_counted_step", counted_step)
        monkeypatch.setattr(Runner, "_slice_stream", staticmethod(sliced))


@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("job", ["prefix", "compaction"])
def test_slices_of_a_step_launch_before_the_next_dispatch(
        monkeypatch, job, depth):
    want = sync_rows(job)  # before the spy, which counts every run
    spy = Spy(monkeypatch)
    run, lines = JOBS[job]
    rows, res = run(lines(), async_depth=depth)
    assert spy.slices
    for k, dispatched, _b, _n in spy.slices:
        # step k is dispatched, step k+1 is not yet
        assert dispatched == k + 1, (k, dispatched)
    assert len({b for _, _, b, _ in spy.slices}) >= 2  # crossed a bucket
    assert tuple(rows) == want
    assert series_total(res, "fetch_slices_behind_step_total") == 0
    assert series_total(res, "fetch_slices_total") > 0


@pytest.mark.parametrize(
    "over",
    [dict(async_depth=2), dict(async_depth=3),
     dict(async_depth=8), dict(async_depth=4, h2d_depth=3)],
    ids=["depth2", "depth3", "depth8", "depth4-h2d3"],
)
@pytest.mark.parametrize("job", ["prefix", "compaction"])
def test_rows_match_the_synchronous_run_in_order(job, over):
    run, lines = JOBS[job]
    rows, res = run(lines(), **over)
    assert rows and tuple(rows) == sync_rows(job)
    if over["async_depth"] == 2:
        # every dispatch retires the step before it, planned first
        assert series_total(res, "fetch_slices_behind_step_total") == 0
    if job == "compaction":
        assert series_total(res, "compaction_spills") > 0


@pytest.mark.parametrize("depth", [1, 2, 3, 4, 8])
def test_each_dispatch_retires_at_most_one_step(monkeypatch, depth):
    """The in-flight window holds at most async_depth - 1 steps. A
    dispatch past it retires the oldest step, and only that one; a
    drain retires what is left one by one. Every step is retired once,
    in dispatch order."""
    want = sync_rows("prefix")  # before the spies
    run_step, retire = Runner._run_step, Runner._retire
    drain = Runner.drain_inflight
    windows = []   # steps in flight after each dispatch
    retired = []   # (step seq, steps dispatched then, in a drain)
    draining = [False]

    def spy_run_step(runner, *args, **kw):
        run_step(runner, *args, **kw)
        windows.append(len(runner._inflight))

    def spy_retire(runner, entry):
        retired.append((entry.seq, runner._dispatched, draining[0]))
        retire(runner, entry)

    def spy_drain(runner):
        draining[0] = True
        try:
            drain(runner)
        finally:
            draining[0] = False

    monkeypatch.setattr(Runner, "_run_step", spy_run_step)
    monkeypatch.setattr(Runner, "_retire", spy_retire)
    monkeypatch.setattr(Runner, "drain_inflight", spy_drain)
    run, lines = JOBS["prefix"]
    rows, _ = run(lines(), async_depth=depth)
    assert rows and tuple(rows) == want
    assert max(windows) == depth - 1
    assert [seq for seq, _, _ in retired] == list(range(1, len(windows) + 1))
    per_dispatch = collections.Counter(
        d for _, d, in_drain in retired if not in_drain
    )
    assert set(per_dispatch.values()) == {1}


@pytest.mark.parametrize("case", ["old-order", "steps-running"])
@pytest.mark.parametrize("job", ["prefix", "compaction"])
def test_behind_counter_counts_slices_queued_after_a_later_step(
        monkeypatch, job, case):
    """The counter counts every slice launched once a later step was
    dispatched. ``old-order``: the fetch planned after the next
    dispatch, as before the split, at async_depth 2 (all slices but
    the last step's, which the end-of-stream drain fetches with no step
    after it). ``steps-running``: at async_depth 3 with no step ever
    finished at the next dispatch, a step is planned only when the
    dispatch that retires it comes, behind the step between. The rows
    do not change."""
    want = sync_rows(job)  # before the spy, which counts every run
    if case == "old-order":
        monkeypatch.setattr(
            Runner, "_slices_fetch", property(lambda r: False))
        depth, lag = 2, 1
    else:
        monkeypatch.setattr(_Inflight, "finished", lambda e: False)
        depth, lag = 3, 2
    spy = Spy(monkeypatch)
    run, lines = JOBS[job]
    rows, res = run(lines(), async_depth=depth)
    assert tuple(rows) == want
    for k, dispatched, _b, _n in spy.slices:
        assert dispatched <= k + 1 + lag, (k, dispatched)
    behind = sum(n for k, d, _b, n in spy.slices if d > k + 1)
    assert behind > 0
    assert series_total(res, "fetch_slices_behind_step_total") == behind
    assert series_total(res, "fetch_slices_total") == sum(
        n for *_, n in spy.slices)
