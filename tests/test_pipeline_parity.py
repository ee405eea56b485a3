"""Deep async pipeline parity: staged H2D uploads (h2d_depth), the
deep dispatch queue (async_depth), device-side output compaction
(compaction_capacity), and the packed narrow wire format (packed_wire)
must all be invisible in the output — byte-identical emissions and a
byte-identical final checkpoint vs the fully synchronous path — and
the compaction spill path must stay exact past its capacity, leaving a
flight-recorder breadcrumb plus a counter when it fires. The p=8 mesh
variant lives at the bottom (slow tier, conftest._SLOW_TESTS).
"""

import glob
import os

import numpy as np
import pytest

from tpustream import StreamExecutionEnvironment, TimeCharacteristic, Tuple2
from tpustream.config import ObsConfig, StreamConfig
from tpustream.runtime.sources import AdvanceProcessingTime, ReplaySource

# strictly synchronous reference: one batch in flight, no staging, no
# compaction, no narrowing — every knob the tentpole added, off
SYNC = dict(
    async_depth=1, h2d_depth=1, compaction_capacity=0, packed_wire=False
)
# everything on, deeper than the defaults
DEEP = dict(async_depth=4, h2d_depth=3)


def parse(line: str) -> Tuple2:
    items = line.split(" ")
    return Tuple2(items[1], int(items[2]))


def rolling_lines(n=40, keys=5):
    return [f"1 k{i % keys} {(i * 7) % 97}" for i in range(n)]


def run_rolling(lines, ckdir=None, obs=None, **over):
    """Keyed rolling sum: main_emission_prefix=False, so its (dense)
    main stream is exactly what the device compaction stage covers."""
    over.setdefault("batch_size", 4)
    cfg = StreamConfig(**over)
    if ckdir is not None:
        cfg = cfg.replace(
            checkpoint_dir=str(ckdir), checkpoint_interval_batches=1
        )
    if obs is not None:
        cfg = cfg.replace(obs=obs)
    env = StreamExecutionEnvironment(cfg)
    handle = (
        env.add_source(ReplaySource(lines))
        .map(parse)
        .key_by(0)
        .sum(1)
        .collect()
    )
    res = env.execute("pipeline-parity")
    return [tuple(t) for t in handle.items], res


CH3 = [
    "2019-08-28T09:00:00 www.163.com 1000",
    "2019-08-28T09:02:00 www.163.com 2000",
    "2019-08-28T09:01:00 www.baidu.com 900",
    "2019-08-28T09:03:00 www.163.com 3000",
    "2019-08-28T09:05:00 www.baidu.com 400",
    "2019-08-28T09:05:30 www.163.com 4000",
    "2019-08-28T09:07:00 www.163.com 500",
    "2019-08-28T09:09:00 www.baidu.com 800",
]


def run_window(lines, **over):
    """Event-time sliding windows (chapter 3): main_emission_prefix, a
    clock-driven flush, and watermarks — the prefix fetch path plus the
    upload-queue flush barriers."""
    from tpustream.jobs.chapter3_bandwidth_eventtime import build

    over.setdefault("batch_size", 2)
    env = StreamExecutionEnvironment(StreamConfig(**over))
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    handle = build(env, env.add_source(ReplaySource(lines))).collect()
    env.execute("pipeline-parity-ch3")
    return handle.items


@pytest.mark.parametrize(
    "variant",
    [
        {},  # the defaults: staging + compaction + packed wire all on
        dict(async_depth=4, h2d_depth=3),
        dict(compaction_capacity=8),
        dict(packed_wire=False, h2d_depth=2),
        dict(async_depth=8, h2d_depth=3),
    ],
    ids=["defaults", "deep-h2d", "tight-compaction", "unpacked", "depth8"],
)
def test_rolling_parity_across_depths(variant):
    lines = rolling_lines()
    want, _ = run_rolling(lines, **SYNC)
    got, _ = run_rolling(lines, **variant)
    assert got == want


@pytest.mark.parametrize(
    "variant",
    [{}, DEEP] + [
        dict(async_depth=a, h2d_depth=h) for a in (3, 8) for h in (1, 3)
    ],
    ids=["defaults", "all-deep", "depth3-h2d1", "depth3-h2d3",
         "depth8-h2d1", "depth8-h2d3"],
)
def test_window_job_parity_across_depths(variant):
    want = run_window(CH3, **SYNC)
    got = run_window(CH3, **variant)
    assert got == want


def median_lines():
    """Three processing-time minutes of per-host cpu samples; each
    minute's window fires at the clock advance that closes it."""
    out = []
    for m in range(3):
        for i in range(12):
            out.append(f"1 10.8.22.{i % 3} cpu0 {(31 * m + 7 * i) % 97}.5")
        out.append(AdvanceProcessingTime((m + 1) * 61_000))
    return out


def run_median(lines, **over):
    """The chapter-2 median: a full-window process(), whose emissions
    the host evaluates against the live device state."""
    from tpustream.jobs.chapter2_median import build

    env = StreamExecutionEnvironment(StreamConfig(batch_size=4, **over))
    handle = build(env, env.add_source(ReplaySource(lines))).collect()
    env.execute("pipeline-parity-median")
    return handle.items


@pytest.mark.parametrize(
    "cls, option", [(StreamConfig, "fetch_group"), (ObsConfig, "adaptive")]
)
def test_removed_pipeline_options_are_rejected(cls, option):
    """The grouped fetch and the adaptive controller are gone: an old
    configuration that sets them fails at construction instead of
    running with another meaning."""
    with pytest.raises(TypeError, match=option):
        cls(**{option: 2})


@pytest.mark.parametrize("depth", [2, 4])
def test_live_state_job_keeps_no_step_in_flight(monkeypatch, depth):
    """A program whose emissions read live state runs synchronously
    at any configured depth: the constructor fixes both windows at 0,
    no step is left in flight after a dispatch, and the rows are the
    synchronous run's."""
    from tpustream.runtime.executor import Runner

    want = run_median(median_lines(), **SYNC)
    run_step = Runner._run_step
    seen = []  # (window, upload lead, steps in flight) after each step

    def spy(runner, *args, **kw):
        run_step(runner, *args, **kw)
        seen.append(
            (runner._max_inflight, runner._h2d_ahead, len(runner._inflight))
        )

    monkeypatch.setattr(Runner, "_run_step", spy)
    got = run_median(median_lines(), async_depth=depth, h2d_depth=depth)
    assert want and got == want
    assert seen and set(seen) == {(0, 0, 0)}


def test_final_checkpoint_identical_sync_vs_deep(tmp_path):
    """Same input at async_depth/h2d_depth 1 vs N: the final
    checkpoint's state arrays (not just the sink output) match
    byte-for-byte — the pipeline may not smear state across snapshot
    barriers."""
    from tpustream.runtime.checkpoint import _META_KEY

    lines = rolling_lines(48, 7)
    want, _ = run_rolling(lines, ckdir=tmp_path / "sync", **SYNC)
    got, _ = run_rolling(lines, ckdir=tmp_path / "deep", **DEEP)
    assert got == want

    def last_arrays(d):
        path = sorted(glob.glob(os.path.join(str(d), "ckpt-*.npz")))[-1]
        with np.load(path) as z:
            return {k: z[k] for k in z.files if k != _META_KEY}

    a, b = last_arrays(tmp_path / "sync"), last_arrays(tmp_path / "deep")
    assert set(a) == set(b)
    for k in sorted(a):
        assert np.array_equal(a[k], b[k]), f"checkpoint leaf {k} diverged"


def test_compaction_overflow_spills_exact():
    """A rolling job emits EVERY record, so batch_size 8 against
    compaction_capacity 2 overflows each step: the spill path must fall
    back to the full fetch (exact output), count every spill, and leave
    one first-spill flight breadcrumb per stream."""
    lines = rolling_lines(64, 3)
    want, _ = run_rolling(lines, **SYNC, batch_size=8)
    got, res = run_rolling(
        lines,
        batch_size=8,
        compaction_capacity=2,
        obs=ObsConfig(enabled=True),
    )
    assert got == want

    series = res.metrics.obs_snapshot()["metrics"]["series"]
    # operator-scoped series carry an operator_ prefix in the snapshot
    spills = [
        s for s in series if s["name"].endswith("compaction_spills")
    ]
    assert spills and sum(s["value"] for s in spills) >= 8  # every batch
    crumbs = [
        e
        for e in res.metrics.job_obs.flight.events()
        if e["kind"] == "compaction_spill"
    ]
    assert len(crumbs) == 1  # first spill only — not one per batch
    assert crumbs[0]["stream"] == "main"
    assert crumbs[0]["capacity"] == 2
    assert crumbs[0]["count"] > 2


def test_compact_fetch_is_exercised_and_counted():
    """Below capacity the compact path (not the spill) serves the
    fetch: zero spills, and the fetched-vs-full byte gauge reflects the
    cut. Guards against the compact branch silently never engaging."""
    lines = rolling_lines(32, 3)
    got, res = run_rolling(
        lines, batch_size=8, obs=ObsConfig(enabled=True)
    )
    want, _ = run_rolling(lines, batch_size=8, **SYNC)
    assert got == want
    series = res.metrics.obs_snapshot()["metrics"]["series"]
    by_suffix = {}
    for s in series:
        if s["type"] in ("counter", "gauge"):
            for want in (
                "compaction_spills", "h2d_bytes_total",
                "fetch_bytes_total", "compaction_ratio",
            ):
                if s["name"].endswith(want):
                    by_suffix[want] = by_suffix.get(want, 0) + s["value"]
    assert by_suffix.get("compaction_spills", 0) == 0
    assert by_suffix.get("h2d_bytes_total", 0) > 0
    assert by_suffix.get("fetch_bytes_total", 0) > 0
    # dense tiny batches can fetch slightly MORE than the full form
    # (pow2 bucket + the index leaf); the gauge just has to be live
    assert by_suffix.get("compaction_ratio", 0) > 0


def test_h2d_spans_traced_when_staged():
    """h2d_depth > 1 with obs on records one ``h2d`` span per staged
    batch in the StepTracer."""
    lines = rolling_lines(24, 3)
    _, res = run_rolling(
        lines, h2d_depth=2, obs=ObsConfig(enabled=True, trace=True)
    )
    snap = res.metrics.obs_snapshot()
    kinds = {e["kind"] for e in snap.get("trace", {}).get("events", [])}
    assert "h2d" in kinds


# --------------------------------------------------------------------------
# p=8 mesh variant (slow tier — registered in conftest._SLOW_TESTS)
# --------------------------------------------------------------------------
def test_sharded_pipeline_parity_p8():
    """The deep pipeline on the 8-shard mesh (single process): staged
    uploads use NamedSharding pre-placement; output must match the
    synchronous mesh run AND the single-chip run."""
    lines = rolling_lines(64, 6)
    p8 = dict(parallelism=8, batch_size=8, key_capacity=64,
              print_parallelism=1)
    want, _ = run_rolling(lines, **SYNC, **p8)
    got, _ = run_rolling(lines, **DEEP, **p8)
    assert got == want
    single, _ = run_rolling(lines, batch_size=8, **SYNC)
    assert sorted(got) == sorted(single)
