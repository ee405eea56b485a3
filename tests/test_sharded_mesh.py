"""Multi-chip SPMD tests on the 8-virtual-device CPU mesh: sharded jobs
must produce exactly the single-chip results (key-owner shards, ICI
all_to_all keyBy, pmax watermark)."""

import numpy as np
import pytest

import jax

from tpustream import StreamExecutionEnvironment, TimeCharacteristic
from tpustream.config import StreamConfig
from tpustream.jobs.chapter2_max import build as build_max
from tpustream.jobs.chapter3_bandwidth_eventtime import build as build_et
from tpustream.runtime.sources import AdvanceProcessingTime, ReplaySource


def et_lines(n_keys=16, n_records=200):
    lines = []
    base_min = 0
    for i in range(n_records):
        minute = i // 20
        sec = (i * 7) % 60
        ch = f"www.ch{i % n_keys}.com"
        flow = 100 + (i % 13) * 10
        lines.append(f"2019-08-28T10:{minute:02d}:{sec:02d} {ch} {flow}")
    return lines


def run_et(lines, parallelism, batch_size=40, key_capacity=64, **cfg_overrides):
    env = StreamExecutionEnvironment(
        StreamConfig(
            parallelism=parallelism,
            batch_size=batch_size,
            key_capacity=key_capacity,
            print_parallelism=1,
            **cfg_overrides,
        )
    )
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    text = env.add_source(ReplaySource(lines))
    h = build_et(env, text).collect()
    env.execute("BandwidthMonitorWithEventTime")
    return sorted((t.f0, round(t.f1, 12)) for t in h.items)


def test_sharded_event_time_window_matches_single_chip():
    lines = et_lines()
    single = run_et(lines, parallelism=1)
    sharded = run_et(lines, parallelism=8)
    assert len(single) > 0
    assert single == sharded


def test_sharded_four_shards():
    lines = et_lines(n_keys=7, n_records=120)
    assert run_et(lines, 1) == run_et(lines, 4)


def run_max(lines, parallelism, batch_size=40):
    env = StreamExecutionEnvironment(
        StreamConfig(
            parallelism=parallelism, batch_size=batch_size, key_capacity=64
        )
    )
    text = env.add_source(ReplaySource(lines))
    h = build_max(env, text).collect()
    env.execute("ComputeCpuMax")
    return h.items


def test_sharded_rolling_max_per_key_sequences_match():
    lines = [
        f"{i} 10.8.22.{i % 5} cpu{i % 3} {30 + ((i * 11) % 60)}.5"
        for i in range(100)
    ]
    single = run_max(lines, 1)
    sharded = run_max(lines, 8)
    assert len(single) == len(sharded) == 100

    def per_key(items):
        d = {}
        for t in items:
            d.setdefault(t.f0, []).append((t.f1, t.f2))
        return d

    assert per_key(single) == per_key(sharded)


def test_exchange_roundtrip_all_records():
    """Direct kernel test: every valid record lands on its owner exactly once."""
    from jax.sharding import PartitionSpec as P

    from tpustream.parallel.exchange import exchange_by_key
    from tpustream.parallel.mesh import AXIS, make_mesh

    s = 8
    b = 64
    mesh = make_mesh(s)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 37, size=b).astype(np.int32)
    vals = rng.normal(size=b)
    ts = rng.integers(0, 1000, size=b).astype(np.int64)
    valid = rng.random(b) > 0.2

    def core(keys, vals, ts, valid):
        cols, v, t, ovf = exchange_by_key(
            [keys, vals], valid, ts, keys, s, b // s
        )
        return cols[0], cols[1], t, v, jax.lax.psum(ovf, AXIS)

    f = jax.jit(
        jax.shard_map(
            core,
            mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
        )
    )
    k2, v2, t2, ok, ovf = jax.device_get(f(keys, vals, ts, valid))
    assert int(np.asarray(ovf).sum()) == 0
    got = sorted(
        (int(k), float(v), int(t))
        for k, v, t, o in zip(k2, v2, t2, ok)
        if o
    )
    want = sorted(
        (int(k), float(v), int(t))
        for k, v, t, o in zip(keys, vals, ts, valid)
        if o
    )
    assert got == want
    # ownership: received records' keys belong to the receiving shard
    rows_per_shard = len(k2) // s
    for d in range(s):
        sl = slice(d * rows_per_shard, (d + 1) * rows_per_shard)
        owned = k2[sl][ok[sl]]
        assert all(int(k) % s == d for k in owned)


def test_sharded_fast_reduce_path_matches_single_chip_exact():
    """The 32-bit scatter-reduce fast path with a per-step fire budget,
    sharded over 8 devices, must equal the exact single-chip results."""
    lines = et_lines()
    exact_single = run_et(lines, parallelism=1)
    fast_sharded = run_et(
        lines,
        parallelism=8,
        acc_dtype="int32",        # scatter-reduce fast path
        max_fires_per_step=2,     # exercise deferred fires sharded
    )
    assert len(exact_single) > 0
    assert exact_single == fast_sharded


# ---------------------------------------------------------------------------
# sharded ProcessWindowFunction (VERDICT round-1 item 4): the median job
# at parallelism N must match single-chip exactly
# (reference chapter2/README.md:177-196)
# ---------------------------------------------------------------------------
def median_lines(n_keys=6, n_records=90):
    lines = []
    for i in range(n_records):
        host = f"10.8.22.{i % n_keys}"
        usage = round(10.0 + ((i * 37) % 89) + 0.5, 1)
        lines.append(f"156345{i:04d} {host} cpu{i % 3} {usage}")
    return lines


def run_median(lines, parallelism, batch_size=40, **cfg_overrides):
    from tpustream.jobs.chapter2_median import build as build_median

    env = StreamExecutionEnvironment(
        StreamConfig(
            parallelism=parallelism,
            batch_size=batch_size,
            key_capacity=64,
            print_parallelism=1,
            process_buffer_capacity=64,
            **cfg_overrides,
        )
    )
    text = env.add_source(ReplaySource(lines))
    h = build_median(env, text).collect()
    env.execute("ComputeCpuMiddle")
    return env, sorted(round(float(v), 9) for v in h.items)


def test_sharded_process_window_matches_single_chip():
    lines = median_lines() + [AdvanceProcessingTime(61_000)]
    env1, single = run_median(lines, parallelism=1)
    env8, sharded = run_median(lines, parallelism=8)
    assert len(single) == 6  # one median per key
    assert single == sharded
    s1, s8 = env1.metrics.summary(), env8.metrics.summary()
    assert s1["window_fires"] == s8["window_fires"] == 6
    assert s8["buffer_overflow"] == 0


def test_sharded_process_window_multiple_windows_and_shard_counts():
    # records spread over two processing-time windows, 4 shards
    lines = (
        median_lines(n_keys=5, n_records=40)
        + [AdvanceProcessingTime(61_000)]
        + median_lines(n_keys=5, n_records=25)
        + [AdvanceProcessingTime(122_000)]
    )
    _, single = run_median(lines, parallelism=1)
    _, sharded = run_median(lines, parallelism=4, batch_size=16)
    assert len(single) == 10
    assert single == sharded
