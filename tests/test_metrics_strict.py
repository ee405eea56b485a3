"""Metrics correctness + strict_overflow failure policy.

VERDICT round-1 items: late drops must be counted even without a late
side output, ``window_fires`` must be wired, emit-latency percentiles
must be tracked, and lossy overflow (keyBy shuffle drops, truncated
process() buffers) must be able to fail the job loudly instead of only
incrementing a counter.
"""

import pytest

from tpustream import StreamExecutionEnvironment, TimeCharacteristic
from tpustream.api.timeapi import Time
from tpustream.api.tuples import Tuple2, Tuple3
from tpustream.api.watermarks import BoundedOutOfOrdernessTimestampExtractor
from tpustream.api.windows import TumblingEventTimeWindows
from tpustream.config import StreamConfig
from tpustream.runtime.sources import AdvanceProcessingTime, ReplaySource


class SecondsExtractor(BoundedOutOfOrdernessTimestampExtractor):
    def __init__(self):
        super().__init__(Time.seconds(0))

    def extract_timestamp(self, line):
        return int(line.split(" ")[0]) * 1000


def parse(line):
    p = line.split(" ")
    return Tuple3(int(p[0]), p[1], int(p[2]))


BASE = 1_200_000  # epoch seconds, multiple of 60


def run_reduce_job(lines, **cfg_overrides):
    env = StreamExecutionEnvironment(
        StreamConfig(batch_size=1, key_capacity=16, **cfg_overrides)
    )
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    text = env.add_source(ReplaySource(lines))
    out = (
        text.assign_timestamps_and_watermarks(SecondsExtractor())
        .map(parse)
        .key_by(1)
        .window(TumblingEventTimeWindows.of(Time.seconds(60)))
        .reduce(lambda a, b: Tuple3(a.f0, a.f1, a.f2 + b.f2))
        .collect()
    )
    env.execute("metrics")
    return out.items, env.metrics.summary()


def test_window_fires_and_late_dropped_without_side_output():
    lines = [
        f"{BASE + 10} www.a.com 100",
        f"{BASE + 10} www.b.com 5",
        f"{BASE + 70} www.a.com 7",    # wm -> BASE+70: [BASE, BASE+60) fires
        f"{BASE + 20} www.a.com 900",  # late: dropped, NO side output here
        f"{BASE + 140} www.a.com 3",
    ]
    rows, s = run_reduce_job(lines)
    # fires: (a, w0), (b, w0), (a, w1) at stream end, (a, w2) at stream end
    assert s["window_fires"] == 4
    assert s["late_dropped"] == 1
    assert s["records_in"] == 5
    assert s["records_emitted"] == len(rows) == 4
    assert s["emit_latency_p99_ms"] > 0.0
    assert s["emit_latency_p99_ms"] >= s["emit_latency_p50_ms"]
    # the dropped 900 must not be in any window sum
    assert all(t.f2 != 1000 for t in rows)


def _median_env(lines, **cfg_overrides):
    env = StreamExecutionEnvironment(StreamConfig(key_capacity=16, **cfg_overrides))
    text = env.add_source(ReplaySource(lines))

    def median(key, ctx, elements, out):
        vals = sorted(e.f2 for e in elements)
        out.collect(vals[len(vals) // 2] if vals else 0.0)

    def parse3(line):
        p = line.split(" ")
        return Tuple3(p[1], p[2], float(p[3]))

    (
        text.map(parse3)
        .key_by(0)
        .time_window(Time.minutes(1))
        .process(median)
        .collect()
    )
    return env


LINES4 = [
    "1563452056 10.8.22.1 cpu0 80.5",
    "1563452050 10.8.22.1 cpu0 78.4",
    "1563452056 10.8.22.1 cpu0 99.9",
    "1563452056 10.8.22.2 cpu1 20.2",
    AdvanceProcessingTime(61_000),
]


def test_process_window_fires_counted():
    env = _median_env(LINES4)
    env.execute("fires")
    s = env.metrics.summary()
    assert s["window_fires"] == 2  # one per key
    assert s["buffer_overflow"] == 0


def test_process_buffer_overflow_counted_not_strict():
    env = _median_env(
        LINES4, process_buffer_capacity=2, strict_overflow=False
    )
    env.execute("overflow-counted")
    s = env.metrics.summary()
    # key 10.8.22.1 had 3 elements, capacity 2 -> 1 truncated
    assert s["buffer_overflow"] == 1


def test_process_buffer_overflow_strict_raises():
    # strict_overflow is the default
    env = _median_env(LINES4, process_buffer_capacity=2)
    with pytest.raises(RuntimeError, match="strict_overflow.*buffer_overflow"):
        env.execute("overflow-strict")


def test_late_to_side_output_not_counted_as_dropped():
    # Flink's numLateRecordsDropped counts only records NOT consumed by a
    # side output; delivered-late records are not drops
    from tpustream.api.output import OutputTag

    lines = [
        f"{BASE + 10} www.a.com 100",
        f"{BASE + 70} www.a.com 7",
        f"{BASE + 20} www.a.com 900",  # late -> side output, NOT dropped
    ]
    tag = OutputTag("late")
    env = StreamExecutionEnvironment(
        StreamConfig(batch_size=1, key_capacity=16)
    )
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    text = env.add_source(ReplaySource(lines))
    w = (
        text.assign_timestamps_and_watermarks(SecondsExtractor())
        .map(parse)
        .key_by(1)
        .window(TumblingEventTimeWindows.of(Time.seconds(60)))
        .side_output_late_data(tag)
    )
    summed = w.reduce(lambda a, b: Tuple3(a.f0, a.f1, a.f2 + b.f2))
    summed.collect()
    late = summed.get_side_output(tag).collect()
    env.execute("late-side")
    assert len(late.items) == 1
    assert env.metrics.summary()["late_dropped"] == 0


def run_sharded_reduce(lines, **cfg_overrides):
    env = StreamExecutionEnvironment(
        StreamConfig(
            batch_size=16,
            key_capacity=64,
            parallelism=8,
            print_parallelism=1,
            **cfg_overrides,
        )
    )
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    text = env.add_source(ReplaySource(lines))
    (
        text.assign_timestamps_and_watermarks(SecondsExtractor())
        .map(parse)
        .key_by(1)
        .window(TumblingEventTimeWindows.of(Time.seconds(60)))
        .reduce(lambda a, b: Tuple3(a.f0, a.f1, a.f2 + b.f2))
        .collect()
    )
    env.execute("sharded-strict")
    return env.metrics.summary()


SKEWED = [f"{BASE + 10} www.hot.com {i}" for i in range(16)] + [
    f"{BASE + 140} www.hot.com 1"
]


def test_exchange_overflow_strict_raises():
    # every record keys to one shard; per-destination slots =
    # factor * local_batch / shards = 0.125 * 16 / 8 = 2 rows < 16
    with pytest.raises(RuntimeError, match="strict_overflow.*exchange_overflow"):
        run_sharded_reduce(
            SKEWED, exchange_capacity_factor=0.125, strict_overflow=True
        )


def test_exchange_overflow_counted_not_strict():
    s = run_sharded_reduce(
        SKEWED, exchange_capacity_factor=0.125, strict_overflow=False
    )
    assert s["exchange_overflow"] > 0


def test_exchange_default_capacity_loss_free_strict_ok():
    s = run_sharded_reduce(SKEWED, strict_overflow=True)
    assert s["exchange_overflow"] == 0
    assert s["window_fires"] == 2  # (hot, w0) and (hot, w2)
