"""chip_smoke.py's parts on the CPU at a tiny size: the stream, the
oracle against a record-at-a-time reference, the job against the
oracle (one lane, two lanes, a 4-shard mesh), and the refusal to run
without a TPU."""

import importlib.util
import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def smoke():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def brute_force(smoke, ts_ms, key, flow, batch):
    """Event by event, batch by batch: the watermark moves after each
    batch, each window end fires once, an event joins the windows that
    have not fired, the end-of-stream watermark fires the rest."""
    wm = -(2**62)
    fired_through = -(2**62)
    windows = {}  # (key, end) -> [sum, fired?]
    late = 0
    rows = []

    def fire(upto):
        for (k, e), w in sorted(windows.items(), key=lambda kv: kv[0][1]):
            if not w[1] and e - 1 <= upto:
                w[1] = True
                if smoke.mbps(w[0]) < 100.0:
                    rows.append((k, w[0]))

    for b0 in range(0, len(ts_ms), batch):
        part = range(b0, min(b0 + batch, len(ts_ms)))
        for i in part:
            t, slide = int(ts_ms[i]), smoke.SLIDE_MS
            ends = range(t // slide * slide + slide,
                         (t + smoke.SIZE_MS) // slide * slide + 1, slide)
            open_ends = [e for e in ends if e - 1 > wm]
            if not open_ends:
                late += 1
            for e in open_ends:
                w = windows.setdefault((int(key[i]), e), [0, False])
                w[0] += int(flow[i])
        wm = max(wm, max(int(ts_ms[i]) for i in part) - smoke.DELAY_MS)
        fire(wm)
    fire(2**62)
    return sorted(rows), late


def test_oracle_matches_record_at_a_time_reference(smoke):
    s = smoke.make_stream(5, 3000, 40, 900, low_share=0.3, late_share=0.05)
    want, want_late = brute_force(smoke, s["ts_ms"], s["key"], s["flow"], 256)
    k, total, late = smoke.oracle(s["ts_ms"], s["key"], s["flow"], 256)
    assert sorted(zip(k.tolist(), total.tolist())) == want
    assert late == want_late and late > 0
    assert want  # some windows alert


def test_lines_render_the_stream(smoke):
    s = smoke.make_stream(1, 1000, 64, 600)
    lines = smoke.render_lines(s["ts_ms"], s["key"], s["flow"])
    first = lines[0].tobytes().decode()
    iso, ch, flow = first.rstrip("\n").split(" ")
    assert ch == smoke.channel(int(s["key"][0])) and int(flow) == s["flow"][0]
    assert iso.startswith("2019-08-28T")
    assert lines.shape == (1000, smoke.LINE_BYTES)


N, KEYS, BATCH = 12000, 512, 2048


@pytest.fixture(scope="module")
def stream(smoke):
    s = smoke.make_stream(2, N, KEYS, 600, low_share=0.2, late_share=0.05)
    lines = smoke.render_lines(s["ts_ms"], s["key"], s["flow"])
    buffers = [
        (lines[i:i + BATCH].tobytes(), min(BATCH, N - i))
        for i in range(0, N, BATCH)
    ]
    return buffers, smoke.oracle(s["ts_ms"], s["key"], s["flow"], BATCH)


# ~100 alerting channels and 72 window ends left at the end of stream:
# the last flush has ~7,000 rows to emit, which 4,096 (and 1,024 per
# shard) alert slots hold only if the flush fires in groups; each data
# step's ~2,000 rows fit either
@pytest.mark.parametrize(
    "lanes,parallelism,alert_capacity",
    [(1, 1, None), (2, 1, None), (1, 4, None), (1, 1, 4096), (1, 4, 1024)],
)
def test_job_matches_oracle(smoke, stream, lanes, parallelism,
                            alert_capacity):
    buffers, (k, total, late) = stream
    kw = {} if alert_capacity is None else {"alert_capacity": alert_capacity}
    rows, summary, _ = smoke.run_job(
        buffers, KEYS, BATCH, lanes=lanes, parallelism=parallelism, **kw
    )
    equal, note = smoke.compare(rows, k, total)
    assert equal, note
    assert summary["late_dropped"] == late > 0
    assert summary["alert_overflow"] == 0


def test_data_step_alert_overflow_raises(smoke, stream):
    # a data step's alerts are not deferred: past the capacity they are
    # lost, and strict_overflow (the default) fails the job
    buffers, _ = stream
    with pytest.raises(RuntimeError, match="alert_overflow"):
        smoke.run_job(buffers, KEYS, BATCH, alert_capacity=1024)


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "'cpu'" in err


def test_sharded_pass_builds_the_step_as_often_as_one_chip(smoke, stream):
    # the initial state and the end-of-stream batch are placed on the
    # mesh as the step returns them, so no later step misses the cache
    buffers, _ = stream
    clock = smoke.CompileClock()
    builds = []
    for parallelism in (1, 4):
        b0 = len(clock.builds)
        smoke.run_job(buffers, KEYS, BATCH, parallelism=parallelism)
        builds.append(
            sum(f == "jit(step)" for _, f in clock.builds[b0:])
        )
    assert builds[1] == builds[0] <= smoke.STEP_BUILDS_MAX, builds
