#!/usr/bin/env python3
"""Chip smoke: the flagship event-time job, end to end on a TPU.

Drives the chapter-3 event-time bandwidth job
(``tpustream/jobs/chapter3_bandwidth_eventtime.py``: ISO timestamps with
1 min bounded out-of-orderness, ``key_by(channel)``, 5 min / 5 s sliding
windows, a ``reduce`` sum, the Mbps map and the ``< 100`` filter)
through ``StreamExecutionEnvironment.execute``: raw text lines go
through the native host parse/intern, pack, H2D, the device step, fetch
and the sink. The stream is made from ``--seed``: 2^22 events over
2^20 channels (the 1M-key deployment, ``key_capacity`` set up front so
the key table never grows) across 10 minutes of event time.

The alert rows are compared with ``oracle``, a numpy model of the same
semantics that does not import ``tpustream``. Passes:

* default (one chip): the stream at ``ingest_lanes=1``, then again at
  ``ingest_lanes=2``, whose rows must be identical in order;
* ``--chips 4``: only the sharded path, the same stream at
  ``parallelism=4`` (keyed state sharded over a 4-device mesh, keyBy as
  an ``all_to_all``), checked against the oracle and for keyed state
  placed on four distinct TPU devices.

Exits non-zero, with no result line, unless JAX's first device is a TPU.
The last line of standard output is the contract line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys
import time

import numpy as np

SIZE_MS = 300_000          # sliding window size (5 min)
SLIDE_MS = 5_000           # slide (5 s)
DELAY_MS = 60_000          # bounded out-of-orderness (1 min)
TZ_S = 8 * 3600            # the job parses ISO local times at UTC+8
BASE_S = 1_566_957_600     # 2019-08-28T10:00:00+08:00
# ~1% of channels carry low flow: 100-110 MB per event, so up to seven
# events in one window stay under 100 Mbps and eight never do. The rest
# carry 800-1000 MB per event, over 100 Mbps with a single event. No
# window sum lands near the 100 Mbps edge.
LOW_FLOW = (100_000_000, 110_000_000)
HIGH_FLOW = (800_000_000, 1_000_000_000)
EVENTS = 1 << 22
KEYS = 1 << 20             # the 1M-key deployment: key_capacity up front
SPAN_S = 600               # event time the stream covers
BATCH = 1 << 16            # bench-sized; the job's default is 8192
LINE_BYTES = 40            # "2019-08-28T10:00:00 ch0000000 123456789\n"
# builds of the step a pass may make (cache loads included): the first
# wire layout, and the one demotion of the timestamp column when the
# delayed events widen a batch's time span past 16-bit deltas. The
# sharded pass is held to the same bound as the single-chip ones.
STEP_BUILDS_MAX = 2


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# the stream
# ---------------------------------------------------------------------------


def make_stream(seed: int, n_events: int, n_keys: int, span_s: int,
                low_share: float = 0.01, late_share: float = 0.005) -> dict:
    """Event arrays in arrival order. Every channel gets the same number
    of events (so all ``n_keys`` appear); event time trails the arrival
    clock by 0-50 s, inside the 60 s bound, except for ``late_share`` of
    the events in the second half, which trail it by 61-450 s. Those
    beyond ~6 min miss every window and are dropped as late; the others
    miss only their earliest windows."""
    rng = np.random.default_rng(seed)
    n = n_events
    key = rng.permutation(np.arange(n) % n_keys).astype(np.int32)
    low = rng.random(n_keys) < low_share
    flow = np.where(
        low[key],
        rng.integers(*LOW_FLOW, size=n),
        rng.integers(*HIGH_FLOW, size=n),
    )
    clock = BASE_S + np.arange(n, dtype=np.int64) * span_s // n
    ts_s = clock - rng.integers(0, 51, size=n)
    late = (rng.random(n) < late_share) & (clock - BASE_S > span_s // 2)
    ts_s[late] = clock[late] - rng.integers(61, 451, size=int(late.sum()))
    return {"ts_ms": ts_s * 1000, "key": key, "flow": flow,
            "low_channels": int(low.sum())}


def _digits(x: np.ndarray, width: int) -> np.ndarray:
    powers = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return (x[:, None] // powers % 10 + ord("0")).astype(np.uint8)


def render_lines(ts_ms: np.ndarray, key: np.ndarray,
                 flow: np.ndarray) -> np.ndarray:
    """``[n, LINE_BYTES]`` uint8: one ``<ISO local time> ch<7 digits>
    <9-digit flow>\\n`` line per event, built column-wise."""
    local = ts_ms // 1000 + TZ_S
    days = np.unique(local // 86400)
    if len(days) != 1 or key.max() >= 10**7 or flow.max() >= 10**9:
        raise ValueError("stream does not fit the fixed-width line layout")
    date = datetime.date(1970, 1, 1) + datetime.timedelta(days=int(days[0]))
    sec = local % 86400
    out = np.empty((len(key), LINE_BYTES), dtype=np.uint8)
    out[:, 0:11] = np.frombuffer(f"{date.isoformat()}T".encode(), np.uint8)
    out[:, 11:13] = _digits(sec // 3600, 2)
    out[:, 14:16] = _digits(sec // 60 % 60, 2)
    out[:, 17:19] = _digits(sec % 60, 2)
    out[:, [13, 16]] = ord(":")
    out[:, 19] = ord(" ")
    out[:, 20:22] = np.frombuffer(b"ch", np.uint8)
    out[:, 22:29] = _digits(key.astype(np.int64), 7)
    out[:, 29] = ord(" ")
    out[:, 30:39] = _digits(flow, 9)
    out[:, 39] = ord("\n")
    return out


def channel(k: int) -> str:
    return f"ch{k:07d}"


# ---------------------------------------------------------------------------
# the oracle (numpy only; no tpustream import)
# ---------------------------------------------------------------------------


def mbps(total):
    """The job's map, evaluated left to right in float64 as Java does."""
    return total * 8.0 / 60 / 1024 / 1024


# bytes per window that make 1 Mbps under the job's map
BYTES_PER_MBPS = 60 * 1024 * 1024 // 8


def oracle(ts_ms: np.ndarray, key: np.ndarray, flow: np.ndarray,
           batch: int, key_chunk: int = 1 << 17):
    """Alert rows ``(channel id, window sum in bytes)`` and the late-drop
    count.

    Semantics of the job at batch granularity: after each batch of
    ``batch`` events in arrival order the watermark becomes
    ``max(previous, max ts of the batch - DELAY_MS)``. A window with end
    ``e`` covers ``[e - SIZE_MS, e)`` and fires once, the first time the
    watermark reaches ``e - 1`` (the end-of-stream watermark fires all
    the rest). An event is dropped as late when every window it belongs
    to had fired before its batch; otherwise it counts in each of its
    windows that had not fired yet. A fired window of a channel emits
    its sum through the Mbps map, and the row is kept when under 100.
    """
    n = len(ts_ms)
    starts = np.arange(0, n, batch)
    batch_max = np.maximum.reduceat(ts_ms, starts)
    wm_after = np.maximum.accumulate(batch_max - DELAY_MS)
    floor = -(2**62)
    wm_before = np.concatenate([[floor], wm_after[:-1]])[np.arange(n) // batch]
    last_end = (ts_ms + SIZE_MS) // SLIDE_MS * SLIDE_MS
    kept = last_end - 1 > wm_before
    # newest end fired before the event's batch: the largest multiple of
    # the slide with end - 1 <= watermark
    fired = np.where(
        wm_before == floor, floor, (wm_before + 1) // SLIDE_MS * SLIDE_MS
    )
    first_end = np.maximum(ts_ms // SLIDE_MS * SLIDE_MS + SLIDE_MS,
                           fired + SLIDE_MS)
    first_k, last_k = first_end[kept], last_end[kept]
    key_k, flow_k = key[kept], flow[kept]
    j0 = int(first_k.min()) // SLIDE_MS
    lo = first_k // SLIDE_MS - j0         # first open end, as a slot
    hi = last_k // SLIDE_MS - j0 + 1      # one past the last end
    n_ends = int(hi.max()) + 1
    n_keys = int(key.max()) + 1
    key_chunk = min(key_chunk, n_keys)
    rows_key, rows_val = [], []
    for k0 in range(0, n_keys, key_chunk):
        sel = (key_k >= k0) & (key_k < k0 + key_chunk)
        kk = (key_k[sel] - k0).astype(np.int64)
        cells = key_chunk * n_ends
        # per-(channel, end) difference arrays: +flow/+1 at the first
        # open end, -flow/-1 one past the last; prefix sums give each
        # window's total and record count
        idx = np.concatenate([kk * n_ends + lo[sel], kk * n_ends + hi[sel]])
        w = np.concatenate([flow_k[sel], -flow_k[sel]]).astype(np.float64)
        c = np.concatenate([np.ones(sel.sum()), -np.ones(sel.sum())])
        total = np.cumsum(
            np.bincount(idx, w, cells).reshape(key_chunk, n_ends), axis=1
        )
        count = np.cumsum(
            np.bincount(idx, c, cells).reshape(key_chunk, n_ends), axis=1
        )
        hit = (count > 0.5) & (mbps(total) < 100.0)
        r, _ = np.nonzero(hit)
        rows_key.append(r + k0)
        rows_val.append(total[hit].astype(np.int64))
    late = int(n - kept.sum())
    return np.concatenate(rows_key), np.concatenate(rows_val), late


def compare(got: list, want_key: np.ndarray, want_sum: np.ndarray) -> tuple:
    """(equal, note) for the rows as multisets.

    XLA folds the map's chain of constant factors into one multiply and
    the TPU emulates float64, so a row's Mbps may differ from Java's
    left-to-right evaluation in the last bits. Each row is therefore
    compared on the exact window sum its Mbps encodes, and its Mbps must
    agree to 1e-12 relative."""
    have = sorted((ch, round(v * BYTES_PER_MBPS), v) for ch, v in got)
    want = sorted(zip((channel(int(k)) for k in want_key), want_sum.tolist()))
    if len(have) != len(want):
        return False, f"{len(have)} rows vs oracle {len(want)}"
    bad = [i for i, (a, b) in enumerate(zip(have, want)) if a[:2] != b]
    if bad:
        i = bad[0]
        return False, (f"{len(bad)} rows differ, first {have[i][:2]} "
                       f"vs oracle {want[i]}")
    err = max(
        (abs(v - mbps(b[1])) / mbps(b[1]) for (_, _, v), b in zip(have, want)),
        default=0.0,
    )
    if err > 1e-12:
        return False, f"Mbps off by {err:.3g} relative"
    return True, f"{len(have)} rows equal (max Mbps relative error {err:.3g})"


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------


class CompileClock:
    """Sums the XLA compile time JAX reports (a backend compile, or a
    load from the persistent cache) and counts persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.builds: list = []     # (seconds, function name)
        self.cache = {"hits": 0, "misses": 0}
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.builds.append((secs, kw.get("fun_name")))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def mark(self) -> tuple:
        return self.seconds, len(self.builds), dict(self.cache)


def state_devices(key_capacity: int) -> list:
    """(shape, devices) of each live array whose length is a multiple of
    ``key_capacity`` above it: the window state's flat pane-ring planes
    (``slots * key_capacity`` cells) and, on a mesh, the per-shard alert
    buffers. At 2^20 keys nothing else is that long."""
    import jax

    return [
        (a.shape, sorted((d.platform, d.id) for d in a.sharding.device_set))
        for a in jax.live_arrays()
        if a.ndim == 1 and a.shape[0] > key_capacity
        and a.shape[0] % key_capacity == 0
    ]


def run_job(buffers: list, key_capacity: int, batch: int, *,
            lanes: int = 1, parallelism: int = 1, **overrides):
    """One run of the flagship job over ``buffers`` (raw byte blocks),
    at the job's default config apart from the arguments.
    Returns (rows, summary, placement): the alert rows as (channel,
    Mbps) in sink order, the job's metrics summary, and where the keyed
    state lived when the first alert reached the sink."""
    from tpustream import StreamExecutionEnvironment, TimeCharacteristic
    from tpustream.config import StreamConfig
    from tpustream.jobs.chapter3_bandwidth_eventtime import build
    from tpustream.runtime.sources import ReplayBytesSource

    cfg = StreamConfig(
        batch_size=batch,
        key_capacity=key_capacity,
        ingest_lanes=lanes,
        parallelism=parallelism,
        **overrides,
    )
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    rows: list = []
    placement: list = []

    def sink(t):
        if not placement:
            placement.extend(state_devices(key_capacity))
        rows.append((t.f0, t.f1))

    build(env, env.add_source(ReplayBytesSource(buffers))).add_sink(sink)
    result = env.execute("BandwidthMonitorWithEventTime")
    return rows, result.summary(), placement


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path, parallelism=4")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, but JAX's first device is "
            f"{dev.platform!r} ({dev.device_kind}); nothing was run",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}")
    clock = CompileClock()

    from tpustream import native

    log(f"native parser loaded: {native.available()}")
    if not native.available():
        print(f"chip_smoke: native parser unavailable: {native.build_error()}",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    s = make_stream(args.seed, EVENTS, KEYS, SPAN_S)
    lines = render_lines(s["ts_ms"], s["key"], s["flow"])
    buffers = [
        (lines[i:i + BATCH].tobytes(), min(BATCH, EVENTS - i))
        for i in range(0, EVENTS, BATCH)
    ]
    log(f"stream: seed={args.seed} events={EVENTS} "
        f"channels={KEYS} low_channels={s['low_channels']} "
        f"event_time_span_s={SPAN_S} bytes={lines.size} "
        f"batch={BATCH} (bench-sized; the job's default is 8192) "
        f"batches={len(buffers)} built_s={time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    want_key, want_val, want_late = oracle(
        s["ts_ms"], s["key"], s["flow"], BATCH
    )
    log(f"oracle: alert_rows={len(want_key)} late_dropped={want_late} "
        f"s={time.perf_counter() - t0:.3f}")

    passes = (
        [("sharded", dict(parallelism=4))]
        if args.chips == 4
        else [("main", {}), ("lanes2", dict(lanes=2))]
    )
    ok = True
    first_rows = None
    for name, kw in passes:
        c0, b0, h0 = clock.mark()
        t0 = time.perf_counter()
        rows, summary, placement = run_job(
            buffers, KEYS, BATCH, **kw
        )
        wall = time.perf_counter() - t0
        c1, b1, h1 = clock.mark()
        equal, note = compare(rows, want_key, want_val)
        late_ok = summary["late_dropped"] == want_late
        log(f"pass {name}: {note}; late_dropped={summary['late_dropped']} "
            f"(oracle {want_late}); records_in={summary['records_in']} "
            f"batches={summary['batches']} wall_s={wall:.3f} "
            f"xla_compile_s={c1 - c0:.3f} rest_s={wall - (c1 - c0):.3f} "
            f"xla_builds={b1 - b0} "
            f"alert_overflow={summary['alert_overflow']} "
            f"cache_hits={h1['hits'] - h0['hits']} "
            f"cache_misses={h1['misses'] - h0['misses']}")
        step_builds = sum(f == "jit(step)" for _, f in clock.builds[b0:b1])
        log(f"pass {name}: jit(step) builds={step_builds} "
            f"(at most {STEP_BUILDS_MAX})")
        slowest = sorted(clock.builds[b0:b1], reverse=True)[:3]
        log(f"pass {name}: slowest XLA builds "
            + ", ".join(f"{f} {t:.3f}s" for t, f in slowest))
        ok &= (
            equal and late_ok and summary["records_in"] == EVENTS
            and summary["alert_overflow"] == 0
            and step_builds <= STEP_BUILDS_MAX
        )
        want_n = kw.get("parallelism", 1)
        devs = {d for _, p in placement for d in p}
        log(f"pass {name}: keyed state arrays "
            f"{sorted({shape for shape, _ in placement})} on devices "
            f"{sorted(devs)}")
        ok &= (
            all(len(p) == want_n for _, p in placement)
            and len(devs) == want_n
            and all(plat == "tpu" for plat, _ in devs)
        )
        if name == "main":
            first_rows = rows
        elif name == "lanes2":
            from tpustream.runtime.ingest import LANE_START_METHOD

            same = rows == first_rows
            log(f"pass lanes2: start_method={LANE_START_METHOD} "
                f"rows identical in order to lanes=1: {same}")
            ok &= same

    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    if not ok:
        log("FAILED")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
