"""Sharded host ingestion (runtime/ingest.py, parallel/lanes.py): lane
worker processes parse line frames in parallel behind shared-memory
rings, and the merge point re-interleaves them in sequence order so the
executor sees the exact stream a single-lane run would produce.

The contract under test: byte-identical output at any lane count
(records, string ids, and the final checkpoint), lossless sticky
transport packing, and exactly-once crash recovery with the lane fleet
in flight."""

import hashlib
import json

import numpy as np
import pytest

from tpustream import StreamExecutionEnvironment
from tpustream.config import ObsConfig, StreamConfig
from tpustream.parallel.lanes import (
    TRANSPORT_CHAINS,
    ShmRing,
    pack_columns,
    unpack_columns,
)
from tpustream.records import BOOL, F64, I64, STR
from tpustream.runtime.checkpoint import load_checkpoint
from tpustream.runtime.sources import ReplaySource
from tpustream.runtime.supervisor import fixed_delay
from tpustream.testing import FaultInjector, FaultPoint

LINES = [
    f"15634520{i:02d} 10.8.22.{i % 5} cpu{i % 3} {40 + (i * 31) % 55}.5"
    for i in range(24)
]


def run_job(lines, ckdir=None, strategy=None, injector=None, **over):
    from tpustream.jobs.chapter2_max import build

    over.setdefault("batch_size", 4)
    cfg = StreamConfig(**over)
    if ckdir is not None:
        cfg = cfg.replace(
            checkpoint_dir=str(ckdir), checkpoint_interval_batches=1
        )
    if injector is not None:
        cfg = injector.install(cfg)
    env = StreamExecutionEnvironment(cfg)
    if strategy is not None:
        env.set_restart_strategy(strategy)
    handle = build(env, env.add_source(ReplaySource(lines))).collect()
    result = env.execute("ingest-lanes-test")
    return env, handle.items, result


def checkpoint_digest(path):
    """Digest of the replayable checkpoint content: device-state leaves
    plus the host cursors that define where the stream resumes. Fields
    that legitimately differ between runs (session id, informational
    ingest cursor) are excluded."""
    ck = load_checkpoint(str(path))
    h = hashlib.sha256()
    for leaf in ck.leaves:
        a = np.asarray(leaf)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    h.update(
        json.dumps(
            [ck.source_pos, ck.emitted, ck.batches], sort_keys=True
        ).encode()
    )
    return h.hexdigest()


# ---------------------------------------------------------------------------
# transport packing: lossless roundtrip + sticky demotion chains
# ---------------------------------------------------------------------------
def test_transport_roundtrip_narrow_modes():
    kinds = [I64, F64, STR, BOOL]
    cols = [
        np.array([1_563_452_000_000, 1_563_452_000_500, 1_563_452_001_000]),
        np.array([80.5, 78.25, -1.0]),
        np.array([0, 1, 2], dtype=np.int32),
        np.array([True, False, True]),
    ]
    sticky = [0, 0, 0, 0]
    metas, payload = pack_columns(cols, kinds, sticky)
    assert [m[0] for m in metas] == ["d16", "f32", "i16", "bits"]
    out = unpack_columns(metas, kinds, payload, 3)
    for a, b in zip(cols, out):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    # every narrow mode keeps the sticky level at the chain head
    assert sticky == [0, 0, 0, 0]


def test_transport_demotion_is_sticky_and_lossless():
    kinds = [I64, F64, STR]
    sticky = [0, 0, 0]
    # frame 1 forces every chain to its widest mode: an int64 span no
    # delta fits, a float f32 cannot represent, a string id >= 2**15
    wide = [
        np.array([0, 1 << 40], dtype=np.int64),
        np.array([0.1, 2.0**53 + 1]),
        np.array([5, 1 << 15], dtype=np.int32),
    ]
    metas, payload = pack_columns(wide, kinds, sticky)
    assert [m[0] for m in metas] == ["raw", "raw", "i32"]
    out = unpack_columns(metas, kinds, payload, 2)
    for a, b in zip(wide, out):
        assert np.array_equal(a, b)
    assert sticky == [
        TRANSPORT_CHAINS[I64].index("raw"),
        TRANSPORT_CHAINS[F64].index("raw"),
        TRANSPORT_CHAINS[STR].index("i32"),
    ]
    # frame 2 WOULD fit the narrow modes, but demotion never reverts —
    # reconciliation at the merge relies on modes only ever widening
    narrow = [
        np.array([10, 11], dtype=np.int64),
        np.array([1.5, 2.5]),
        np.array([0, 1], dtype=np.int32),
    ]
    metas2, payload2 = pack_columns(narrow, kinds, sticky)
    assert [m[0] for m in metas2] == ["raw", "raw", "i32"]
    out2 = unpack_columns(metas2, kinds, payload2, 2)
    for a, b in zip(narrow, out2):
        assert np.array_equal(a, b)


def test_transport_i64_intermediate_rung():
    # a span that overflows uint16 deltas but fits int32 lands on d32,
    # and a later d16-able frame stays at d32 (sticky, one-way)
    kinds = [I64]
    sticky = [0]
    mid = np.array([0, 1 << 20], dtype=np.int64)
    metas, payload = pack_columns([mid], kinds, sticky)
    assert metas[0][0] == "d32"
    assert np.array_equal(unpack_columns(metas, kinds, payload, 2)[0], mid)
    metas2, _ = pack_columns([np.array([3, 4], dtype=np.int64)], kinds, sticky)
    assert metas2[0][0] == "d32"


def test_transport_empty_and_nan_columns():
    kinds = [I64, F64]
    sticky = [0, 0]
    cols = [np.empty(0, np.int64), np.array([np.nan, 1.0])]
    metas, payload = pack_columns(cols, kinds, sticky)
    out = unpack_columns(metas, kinds, payload, 0)
    assert len(out[0]) == 0
    # the canonical NaN round-trips through f32 BIT-exactly, so it demotes
    assert metas[1][0] == "f32"
    assert np.array_equal(
        out[1].view(np.int64), cols[1].view(np.int64)
    )


def test_transport_f64_nan_payload_rides_raw():
    # a NaN with non-default payload bits is VALUE-equal after an f32
    # round trip (any NaN == any NaN under equal_nan) but not BIT-equal:
    # it must not demote, or transport would rewrite its bit pattern
    weird_nan = np.array([0x7FF8000000000001], dtype=np.int64).view(
        np.float64
    )
    cols = [np.concatenate([weird_nan, [1.0]])]
    sticky = [0]
    metas, payload = pack_columns(cols, [F64], sticky)
    assert metas[0][0] == "raw"
    out = unpack_columns(metas, [F64], payload, 2)
    assert np.array_equal(out[0].view(np.int64), cols[0].view(np.int64))


# ---------------------------------------------------------------------------
# shared-memory ring: framing, credit flow, wrap, corruption check
# ---------------------------------------------------------------------------
def test_shm_ring_write_read_credit_and_wrap():
    ring = ShmRing(64)
    try:
        credits = []

        def wait_credit():
            assert credits, "ring blocked with no outstanding credit"
            return credits.pop(0)

        p1, p2, p3 = b"a" * 16, b"b" * 16, b"c" * 16
        off1, cost1 = ring.write(p1, wait_credit)
        off2, cost2 = ring.write(p2, wait_credit)
        assert (off1, cost1) == (0, 24) and (off2, cost2) == (24, 24)
        assert ring.read(off1, 16) == p1 and ring.read(off2, 16) == p2
        # reader acks frame 1; the third write must wrap (head 48 + 24 >
        # 64), so its cost includes the skipped 16-byte tail
        credits.append(cost1)
        off3, cost3 = ring.write(p3, wait_credit)
        assert off3 == 0 and cost3 == 24 + (64 - 48)
        assert ring.read(off3, 16) == p3
        assert not credits, "writer must consume the pending credit"
        # a descriptor/length mismatch is corruption, not silent data
        with pytest.raises(RuntimeError, match="corrupt"):
            ring.read(off3, 15)
        assert ring.fits(64 - ring.HEADER) and not ring.fits(64)
    finally:
        ring.close()


def test_shm_ring_large_frame_after_wrap_drains_and_resets():
    """A frame larger than the space past head wraps; when the wrap cost
    (frame + skipped tail) exceeds the whole ring, the writer must drain
    fully and restart at offset 0 instead of waiting for credit that can
    never arrive (regression: this used to deadlock the lane and hang
    the merge)."""
    ring = ShmRing(1000)
    try:
        credits = []

        def wait_credit():
            assert credits, "ring blocked with no outstanding credit"
            return credits.pop(0)

        off1, cost1 = ring.write(b"a" * 400, wait_credit)
        assert ring.read(off1, 400) == b"a" * 400
        credits.append(cost1)
        # 600B frame at head 408: wrap cost would be 608 + 592 = 1200,
        # more than the ring itself — free can never satisfy it
        off2, cost2 = ring.write(b"b" * 600, wait_credit)
        assert (off2, cost2) == (0, ring.HEADER + 600)
        assert ring.read(off2, 600) == b"b" * 600
        assert not credits, "drain must consume the pending credit"
        # and the ring keeps working from the reset head
        credits.append(cost2)
        off3, cost3 = ring.write(b"c" * 900, wait_credit)
        assert off3 == 0 and ring.read(off3, 900) == b"c" * 900
    finally:
        ring.close()


# ---------------------------------------------------------------------------
# lane worker protocol: oversized frames must not skew the string remap
# ---------------------------------------------------------------------------
def test_worker_oversized_frame_keeps_string_remap_aligned():
    """When a packed payload cannot ever fit the output ring the worker
    host-routes the frame; the strings that frame interned must NOT be
    marked shipped — they ride out with the lane's next shipped frame,
    so the merge's lane->global remap stays aligned (regression: shipped
    advanced before the fits() check, silently corrupting every later
    frame's string ids)."""
    import queue
    import threading

    from tpustream.hostparse import PExpr
    from tpustream.parallel.lanes import LaneSpec, lane_worker_main

    spec = LaneSpec(
        exprs=[PExpr.field(" ", 0), PExpr("parse_f64", (PExpr.field(" ", 1),))],
        kinds=[STR, F64],
        str_slots=[True, False],
    )
    ev, _ = spec.build_evaluator()
    if ev is None:
        pytest.skip("native parser unavailable")
    in_ring = ShmRing(1 << 16)
    # 64-byte output ring: an 8-byte header leaves 56 payload bytes, so
    # frame 0 below (30 rows -> 60B i16 + 120B f32) can NEVER fit
    out_ring = ShmRing(64)
    in_q, out_q = queue.Queue(), queue.Queue()
    ack_in, ack_out = queue.Queue(), queue.Queue()
    stop_ev = threading.Event()
    worker = threading.Thread(
        target=lane_worker_main,
        args=(0, spec, in_ring.name, in_ring.size, out_ring.name,
              out_ring.size, in_q, out_q, ack_in, ack_out, stop_ev),
        daemon=True,
    )
    worker.start()
    try:
        def send(seq, lines):
            data = "\n".join(lines).encode("utf-8")
            off, cost = in_ring.write(data, lambda: ack_in.get(timeout=10))
            in_q.put(("frame", seq, off, cost, len(data), len(lines)))

        # frame 0: 30 distinct strings, packed payload 180B > 56B
        send(0, [f"s{i} {i}.5" for i in range(30)])
        reply = out_q.get(timeout=10)
        assert reply == ("host", 0)
        # frame 1: reuses s0/s1 and interns s30/s31; fits (24B)
        send(1, ["s0 0.5", "s30 1.5", "s1 2.5", "s31 3.5"])
        reply = out_q.get(timeout=10)
        assert reply[0] == "frame" and reply[1] == 1, reply
        _, _, off, cost, nbytes, n, metas, new_strings, _ = reply
        # the host-routed frame's 30 strings ship here, ahead of the new
        # ones, in first-seen order — exactly the lane-local id order
        assert new_strings[0] == [f"s{i}" for i in range(30)] + ["s30", "s31"]
        assert new_strings[1] is None
        payload = out_ring.read(off, nbytes)
        ack_out.put(cost)
        cols = unpack_columns(metas, spec.kinds, payload, n)
        assert [new_strings[0][i] for i in cols[0]] == [
            "s0", "s30", "s1", "s31"
        ]
    finally:
        in_q.put(("stop",))
        stop_ev.set()
        worker.join(timeout=10)
        in_ring.close()
        out_ring.close()


def test_merge_remap_grow_array():
    """The merge-side lane->global remap appends into a grow-by-doubling
    int32 array and gathers through the live prefix (a plain list would
    re-materialize O(all strings) per frame — quadratic over a stream)."""
    from tpustream.records import _Remap

    r = _Remap()
    expect = []
    for start in range(0, 1200, 100):
        ids = list(range(start * 7, (start + 100) * 7, 7))
        r.extend(ids)
        expect.extend(ids)
        got = r.view()
        assert got.dtype == np.int32 and got.tolist() == expect
    assert np.array_equal(
        r.view()[np.array([0, 599, 1199])],
        np.array([expect[0], expect[599], expect[1199]]),
    )


# ---------------------------------------------------------------------------
# end-to-end parity: multi-lane output and checkpoints match single-lane
# ---------------------------------------------------------------------------
def test_two_lane_output_and_checkpoint_parity(tmp_path):
    _, base, _ = run_job(LINES, ckdir=tmp_path / "one")
    env, multi, res = run_job(
        LINES, ckdir=tmp_path / "two", ingest_lanes=2,
        obs=ObsConfig(enabled=True),
    )
    assert multi == base, "multi-lane output diverged from single-lane"
    # prove the plane actually engaged — a silently disabled plane would
    # pass the parity assertion without testing anything
    kinds = [e["kind"] for e in res.metrics.job_obs.flight.events()]
    assert "ingest_lanes_enabled" in kinds, kinds
    series = res.metrics.obs_snapshot()["metrics"]["series"]
    lane_counts = {
        s["labels"]["lane"]: s["value"]
        for s in series
        if s["name"] == "ingest_lane_records_total"
    }
    assert set(lane_counts) == {"0", "1"}
    assert sum(lane_counts.values()) == len(LINES)
    # the replayable checkpoint content must be byte-identical too
    assert checkpoint_digest(tmp_path / "one") == checkpoint_digest(
        tmp_path / "two"
    )


def test_four_lane_crash_recovery_exactly_once(tmp_path):
    """device_step fault at step 2 with ingest_lanes=4: the supervisor
    kills the lane fleet with the attempt, restarts from the latest
    auto-checkpoint, and the recovered output is byte-identical to an
    uninterrupted single-lane run — frames still in a lane ring at the
    crash are replayed exactly once via the source cursor."""
    _, full, _ = run_job(LINES)
    inj = FaultInjector(FaultPoint("device_step", at=2))
    _, out, res = run_job(
        LINES, ckdir=tmp_path, strategy=fixed_delay(3, 0.0), injector=inj,
        ingest_lanes=4, obs=ObsConfig(enabled=True),
    )
    assert inj.fired == 1
    assert out == full, "recovered multi-lane output diverged"
    kinds = [e["kind"] for e in res.metrics.job_obs.flight.events()]
    # the plane engaged on the first attempt AND after the restart
    assert kinds.count("ingest_lanes_enabled") == 2, kinds
    for want in ("job_failed", "job_restarting", "job_recovered"):
        assert want in kinds, kinds
