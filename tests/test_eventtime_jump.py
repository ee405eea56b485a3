"""Event-time jumps larger than the pane ring must preserve exact window
semantics (reduce/aggregate path) or fail safe (full-window buffers).

Scenario: the reference transcript's shape (chapter3/README.md:283-297) —
in-order records, one late straggler, then a 61-minute jump. The jump
spans ~732 panes over an ~88-slot ring: before the sweep fix, old and
new panes aliased the same slot mod N (impossible window sums like
old+new across a >5-minute span) and due-but-unfired ends were evicted.
Expected counts below are hand-enumerated sliding-window compositions
((5 min, 5 s) windows, bounded out-of-orderness 1 min).
"""

import collections

import pytest

from tpustream import StreamExecutionEnvironment, TimeCharacteristic
from tpustream.config import StreamConfig
from tpustream.jobs.chapter3_bandwidth_eventtime import build as build_ch3
from tpustream.runtime.sources import ReplaySource

LINES = [
    "2019-08-28T09:03:00 www.163.com 1000",
    "2019-08-28T09:04:00 www.163.com 2000",
    "2019-08-28T09:05:00 www.163.com 3000",
    "2019-08-28T09:01:00 www.163.com 9999",  # late once wm passes 09:04
    "2019-08-28T10:06:00 www.163.com 5000",  # 61-minute jump
]


def _run(batch_size, parallelism=1):
    cfg = StreamConfig(batch_size=batch_size, parallelism=parallelism)
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    h = build_ch3(env, env.add_source(ReplaySource(LINES))).collect()
    env.execute("jump")
    sums = collections.Counter(
        round(t.f1 * 60 * 1024 * 1024 / 8) for t in h.items
    )
    return dict(sums), env.metrics.summary()


def test_jump_single_batch_exact_windows():
    # all five records in one batch: nothing is late (the watermark only
    # advances after the batch), so the 9999 straggler joins every
    # window covering 09:01
    sums, m = _run(batch_size=8)
    assert sums == {
        9999: 24,   # ends 09:01:05..09:03:00: {9999}
        10999: 12,  # ends 09:03:05..09:04:00: {9999,1000}
        12999: 12,  # ends 09:04:05..09:05:00: {9999,1000,2000}
        15999: 12,  # ends 09:05:05..09:06:00: {9999,1000,2000,3000}
        6000: 24,   # ends 09:06:05..09:08:00: {1000,2000,3000}
        5000: 72,   # ends 09:08:05..09:09:00: {2000,3000} (12)
                    # + ends 10:06:05..10:11:00: {5000} (60, EOS flush)
        3000: 12,   # ends 09:09:05..09:10:00: {3000}
    }
    assert m["evicted_unfired"] == 0
    assert m["late_dropped"] == 0


def test_jump_per_record_batches_exact_windows():
    # one record per batch: wm reaches 09:04 before the 9999 straggler
    # arrives, so windows ending <= 09:04 never see it (but it is NOT
    # fully late: its open windows admit it — Flink's per-window rule)
    sums, m = _run(batch_size=1)
    assert sums == {
        1000: 12,   # ends 09:03:05..09:04:00 fired at wm 09:04: {1000}
        12999: 12,  # ends 09:04:05..09:05:00: {1000,2000,9999}
        15999: 12,  # ends 09:05:05..09:06:00: {+3000}
        6000: 24,   # ends 09:06:05..09:08:00
        5000: 72,   # {2000,3000} x12 + {5000} x60
        3000: 12,   # ends 09:09:05..09:10:00
    }
    assert m["evicted_unfired"] == 0
    assert m["late_dropped"] == 0


def test_jump_sharded_matches_single_chip():
    want, _ = _run(batch_size=4)
    got, m = _run(batch_size=4, parallelism=4)
    assert got == want
    assert m["evicted_unfired"] == 0


def test_jump_full_window_process_fails_safe():
    # the median (full-window process()) path cannot sweep a jump — it
    # must drop the uncoverable records LOUDLY instead of aliasing them
    # into live buffers
    from tpustream.jobs.chapter2_median import build as build_median

    lines = [
        "1565000000 10.8.22.1 cpu0 10.0",
        "1565000001 10.8.22.1 cpu0 20.0",
    ]
    late_by_an_hour = "1564996400 10.8.22.1 cpu0 99.0"

    env = StreamExecutionEnvironment(
        StreamConfig(batch_size=1, key_capacity=8, strict_overflow=False)
    )
    env.set_stream_time_characteristic(TimeCharacteristic.IngestionTime)
    src = ReplaySource(
        lines + [late_by_an_hour],
        start_ms=1565000000_000,
        ms_per_record=3_600_000,  # 1 h of processing time per record
    )
    h = build_median(env, env.add_source(src)).collect()
    env.execute("median-jump")
    # every emitted median is a real per-window median of actual inputs
    for t in h.items:
        v = t if isinstance(t, float) else t.f1
        assert v in (10.0, 15.0, 20.0, 99.0)
    m = env.metrics.summary()
    assert m["evicted_unfired"] + m["late_dropped"] >= 1
