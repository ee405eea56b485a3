"""Step-tagged tracing of the flagship event-time job: every span a
pulled batch records carries that batch's step id from the source pull
to the sink, ``parse`` holds the native call and the id remap as
sub-spans and ``emit`` the column conversion, the bridged profiler events are the ring's spans one to one
on a fixed clock offset, and the compiled step's named scopes come back
as an op-to-scope map. With obs off nothing is recorded."""

import collections
import glob
import time

import jax
import numpy as np
import pytest

from tpustream import StreamExecutionEnvironment, TimeCharacteristic
from tpustream.config import ObsConfig, StreamConfig
from tpustream.jobs.chapter3_bandwidth_eventtime import build
from tpustream.obs.profiler import PipelineProfiler
from tpustream.obs.tracing import NULL_TRACER, StepTracer
from tpustream.runtime.sources import IterableSource, ReplayBytesSource

PULLED_KINDS = ("source", "parse", "pack", "h2d", "dispatch", "emit")


def buffers(n_buf=14, lines=24, channels=6):
    """Raw byte blocks of ``ts channel flowBytes`` lines, 20 s of event
    time apart, so 5 min / 5 s windows fire while the stream runs."""
    out = []
    t0 = np.datetime64("2019-08-28T09:00:00")
    for b in range(n_buf):
        rows = []
        for i in range(lines):
            ts = t0 + np.timedelta64(20 * b + i % 20, "s")
            rows.append(f"{ts} c{(b + i) % channels} {100 + 7 * i}")
        out.append(("\n".join(rows).encode() + b"\n", len(rows)))
    return out


def run(obs, **over):
    over.setdefault("batch_size", 32)
    cfg = StreamConfig(key_capacity=64, alert_capacity=256, obs=obs, **over)
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    rows = []
    build(env, env.add_source(ReplayBytesSource(buffers()))).add_sink(
        lambda t: rows.append((t.f0, t.f1))
    )
    res = env.execute("step-tracing")
    return rows, res


DEEP = dict(h2d_depth=2, async_depth=3)
# the widest a run's bracket around an annotation's clock read may be
# for it to test the twinning (StepTracer.bridge_skew_max_s): a thread
# descheduled there by other processes on the cores moves one twin by
# the whole pause, which says nothing about the tracer
SKEW_S = 50e-6


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One deep-pipelined run with the bridge on, under the profiler:
    the first of up to five whose every span read the two clocks within
    ``SKEW_S`` of each other."""
    for _ in range(5):
        logdir = str(tmp_path_factory.mktemp("prof"))
        with jax.profiler.trace(logdir):
            rows, res = run(
                ObsConfig(enabled=True, profiler_bridge=True,
                          trace_ring_size=1 << 14), **DEEP,
            )
        if res.metrics.job_obs.tracer.bridge_skew_max_s < SKEW_S:
            break
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    return rows, res.metrics.job_obs, path


def spans_of(job_obs):
    tr = job_obs.tracer
    assert tr.total_spans == len(tr.raw_tail(tr.total_spans))  # no drops
    return tr.raw_tail(tr.total_spans)


def test_every_span_of_a_pulled_batch_carries_its_step(traced):
    rows, job_obs, _ = traced
    assert rows  # windows fired while the stream ran
    spans = spans_of(job_obs)
    by_step = collections.defaultdict(collections.Counter)
    for kind, step, _op, _t0, _dur in spans:
        assert step >= 1, (kind, step)
        by_step[step][kind] += 1
    fed = [s for s, c in by_step.items() if c["pack"]]
    assert len(fed) == len(buffers())
    for s in fed:
        c = by_step[s]
        for kind in PULLED_KINDS + ("parse.native", "parse.remap"):
            assert c[kind] == 1, (s, kind, dict(c))
    # steps no pulled batch fed (the end-of-stream flush) drew fresh
    # ids: dispatched and emitted once, never pulled
    fresh = [s for s, c in by_step.items() if c["dispatch"] and not c["source"]]
    assert fresh
    for s in fresh:
        assert by_step[s]["dispatch"] == 1 and by_step[s]["emit"] == 1


def test_parse_ahead_source_wait_carries_the_step():
    """With parse-ahead the ``source`` span is the consumer's queue
    wait; it learns its step when the batch arrives."""
    _, res = run(ObsConfig(enabled=True), parse_ahead=2, **DEEP)
    by_step = collections.defaultdict(collections.Counter)
    for kind, step, *_ in spans_of(res.metrics.job_obs):
        by_step[step][kind] += 1
    fed = [s for s, c in by_step.items() if c["pack"]]
    assert len(fed) == len(buffers())
    for s in fed:
        for kind in PULLED_KINDS:
            assert by_step[s][kind] == 1, (s, kind, dict(by_step[s]))


def test_fetch_carries_the_last_step_of_its_group(traced):
    _, job_obs, _ = traced
    spans = spans_of(job_obs)
    # a step planned before the next dispatch records its own ``fetch``
    # span then (its step's id, or the last one's when it plans several
    # finished steps); the retiring ``fetch``, just before the step's
    # emit, carries that one step
    groups = []  # (steps of the fetch spans, steps emitted after them)
    for kind, step, _op, _t0, _dur in spans:
        if kind == "fetch":
            if not groups or groups[-1][1]:
                groups.append(([], []))
            groups[-1][0].append(step)
        elif kind == "emit":
            groups[-1][1].append(step)
    assert groups
    assert any(len(fetched) > 1 for fetched, _ in groups)
    dispatched = [s for k, s, *_ in spans if k == "dispatch"]
    for fetched, emitted in groups:
        assert emitted == fetched[-1:]  # one step retired per fetch
        assert set(fetched) <= set(dispatched)
    emitted = [s for _, e in groups for s in e]
    assert emitted == dispatched  # each step emits once, in order


def test_intern_counter_counts_each_channel_once(traced):
    """``intern_new_ids_total`` counts native ids appended to the remap:
    each channel once, however many batches carry it again."""
    _, job_obs, _ = traced
    assert job_obs.counter("intern_new_ids_total").value == 6


def test_parse_sub_spans_nest_inside_parse(traced):
    _, job_obs, _ = traced
    spans = spans_of(job_obs)
    parse = {s: (t0, t0 + d) for k, s, _o, t0, d in spans if k == "parse"}
    subs = [(k, s, t0, d) for k, s, _o, t0, d in spans
            if k in ("parse.native", "parse.remap")]
    assert subs
    for _k, s, t0, d in subs:
        lo, hi = parse[s]
        assert lo <= t0 and t0 + d <= hi


def test_emit_format_nests_inside_its_emit(traced):
    """``emit.format`` (the main stream's fetched columns converted to
    Python values) sits inside the ``emit`` span of its step, once for
    each emit that handed rows to the sink."""
    _, job_obs, _ = traced
    spans = spans_of(job_obs)
    emits = {s: (t0, t0 + d) for k, s, _o, t0, d in spans if k == "emit"}
    fmts = [(s, t0, d) for k, s, _o, t0, d in spans if k == "emit.format"]
    assert fmts
    assert len({s for s, _, _ in fmts}) == len(fmts)
    for s, t0, d in fmts:
        lo, hi = emits[s]
        assert lo <= t0 and t0 + d <= hi


def test_bridged_events_twin_the_ring_on_one_clock(traced):
    _, job_obs, path = traced
    assert job_obs.tracer.bridge_skew_max_s < SKEW_S
    data = jax.profiler.ProfileData.from_file(path)
    # (kind, step) names one span, but for ``fetch``: a step planned
    # before the next dispatch and its retiring fetch share a step id;
    # such twins pair in time order
    twins = collections.defaultdict(list)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith("tpustream."):
                    continue
                stats = dict(e.stats)
                key = (e.name[len("tpustream."):], int(stats["step"]))
                twins[key].append((e.start_ns, e.duration_ns, stats))
    for key, events in twins.items():
        assert key[0] == "fetch" or len(events) == 1, key
        events.sort(key=lambda ev: ev[0])
    spans = spans_of(job_obs)
    assert sum(map(len, twins.values())) == len(spans)
    epoch = job_obs.tracer.epoch
    offsets = []
    paired = collections.Counter()
    for kind, step, _op, t0, dur in spans:
        start_ns, dur_ns, stats = twins[(kind, step)][paired[(kind, step)]]
        paired[(kind, step)] += 1
        assert abs(dur_ns / 1e9 - dur) < 100e-6, (kind, step)
        offsets.append(start_ns / 1e9 - (epoch + t0))
        if kind == "emit":
            assert "rows" in stats
    assert max(offsets) - min(offsets) < 100e-6
    rows_emitted = sum(int(dict(s)["rows"]) for (k, _), evs
                       in twins.items() if k == "emit" for _, _, s in evs)
    assert rows_emitted == len(traced[0])


def test_scope_map_names_each_phase_of_the_step(traced):
    _, job_obs, _ = traced
    scopes = job_obs.op_scopes.get("jit_step")
    assert scopes
    named = set(scopes.values())
    for want in ("unpack", "scatter", "fire", "compact"):
        assert want in named, (want, sorted(named))


def test_scope_map_names_the_rolling_phases():
    """The rolling step (chapter-2 running max) opens ``combine`` (sort,
    scan, compare) and ``state`` (gathers, plane scatters) between the
    executor's ``unpack`` and ``compact``."""
    from tpustream.jobs.chapter2_max import build as build_max

    env = StreamExecutionEnvironment(StreamConfig(
        batch_size=8, key_capacity=64,
        obs=ObsConfig(enabled=True, profiler_bridge=True)))
    lines = [f"1566957600 host{i % 5} cpu{i % 4} {10 + i}.5"
             for i in range(24)]
    build_max(env, env.from_collection(lines)).collect()
    res = env.execute("rolling-scopes")
    named = set(res.metrics.job_obs.op_scopes["jit_step"].values())
    for want in ("unpack", "combine", "state", "compact"):
        assert want in named, (want, sorted(named))


def test_scopes_and_spans_leave_the_output_unchanged(traced):
    rows, _ = run(ObsConfig(), **DEEP)
    assert rows == traced[0]


def test_null_tracer_records_nothing():
    rows, res = run(ObsConfig(), **DEEP)
    assert rows
    tr = res.metrics.job_obs.tracer
    assert tr is NULL_TRACER
    assert tr.total_spans == 0 and tr.events() == [] and tr.raw_tail(9) == []
    assert tr.new_step() == -1 and not tr.bridged
    with tr.span("emit", 3) as sp:
        sp.note(step=4, rows=1)
    assert tr.total_spans == 0 and res.metrics.job_obs.op_scopes is None


def test_profiler_leaves_waits_and_sub_spans_out_of_stage_shares():
    tr = StepTracer(64)
    t = [100.0]
    prof = PipelineProfiler(tr, window_s=1e6, clock=lambda: t[0])
    for kind, dur in (("source", 5.0), ("parse", 2.0), ("parse.native", 1.5),
                      ("parse.remap", 0.4), ("dispatch", 2.0),
                      ("emit.format", 0.3)):
        tr._record(kind, 1, "", tr.epoch, dur)
    t[0] = tr.epoch + 10.0
    p = prof.profile()
    assert set(p["waits"]) == {"source", "parse.native", "parse.remap",
                               "emit.format"}
    assert "source" not in p["stages"] and "parse.native" not in p["stages"]
    assert "emit.format" not in p["stages"]
    assert p["stages"]["parse"]["share"] == pytest.approx(0.5)
    assert sum(s["share"] for s in p["stages"].values()) == pytest.approx(1.0)
    assert p["waits"]["source"]["total_ms"] == pytest.approx(5000.0)


def test_source_stall_reads_the_source_span():
    """A source quiet for longer than the stall limit leaves one
    source_stall event whose gap is that step's ``source`` span."""
    def slow():
        for i in range(3):
            if i == 2:
                time.sleep(1.2)
            yield f"2019-08-28T09:0{i}:00 c0 100"

    cfg = StreamConfig(batch_size=4, max_batch_delay_ms=0.0,
                       obs=ObsConfig(enabled=True))
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    build(env, env.add_source(IterableSource(slow()))).collect()
    res = env.execute("stall")
    job_obs = res.metrics.job_obs
    stalls = [e for e in job_obs.flight.events() if e["kind"] == "source_stall"]
    assert len(stalls) == 1
    src = {s: d for k, s, _o, _t, d in spans_of(job_obs) if k == "source"}
    assert stalls[0]["gap_s"] == pytest.approx(src[stalls[0]["step"]], abs=1e-3)
