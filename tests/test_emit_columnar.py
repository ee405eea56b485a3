"""The host's hand-off of fetched emission columns to the sinks.

``EmissionFormatter`` converts each column once, ``iter_rows`` builds
the rows from the converted lists as a sink takes them, and each
branch's sink takes a step's rows in one ``emit_many`` call. These tests pin that path to the row-by-row one it
replaced: the same values, the same Python types, the same order, and
``print()`` output byte for byte. The row-by-row reference here is that
path's own code (a cast per field, a ``TupleN(...)`` per row, one
``emit`` per row). Whole jobs compare the batched hand-off (obs on,
ledger off) with the row-by-row fan-out the executor keeps for the
ledger (obs on, ledger on), and ``emit_rows_batched_total`` tells the
two apart.
"""

import hashlib
import io
import pickle
import weakref

import numpy as np
import pytest

from tpustream import (
    BoundedOutOfOrdernessTimestampExtractor,
    StreamExecutionEnvironment,
    Time,
    TimeCharacteristic,
    Tuple2,
    Tuple3,
    Tuple4,
)
from tpustream.api.output import OutputTag
from tpustream.api.tuples import _java_str, make_tuple
from tpustream.api.windows import TumblingEventTimeWindows
from tpustream.config import ObsConfig, StreamConfig
from tpustream.jobs.chapter3_bandwidth_eventtime import build
from tpustream.obs.ledger import encode_row
from tpustream.records import BOOL, F64, I64, STR, DerivedKeyTable, StringTable
from tpustream.runtime.executor import _apply_ops, _apply_ops_many
from tpustream.runtime.sinks import (
    CollectSink,
    EmissionFormatter,
    FnSink,
    PrintSink,
    RetryingSink,
    iter_rows,
)
from tpustream.runtime.sources import ReplayBytesSource, ReplaySource
from tpustream.testing import FaultInjector, FaultPoint

# -- the row-by-row path, as the executor ran it before ---------------------


def reference_rows(kinds, tables, cols):
    n = len(cols[0]) if cols else 0
    converted = []
    for kind, col, table in zip(kinds, cols, tables):
        if kind == STR:
            converted.append(
                [table.lookup(int(i)) if int(i) >= 0 else None for i in col]
            )
        elif kind == F64:
            converted.append([float(v) for v in col])
        elif kind == BOOL:
            converted.append([bool(v) for v in col])
        else:
            converted.append([int(v) for v in col])
    out = []
    for j in range(n):
        vals = tuple(c[j] for c in converted)
        if len(vals) == 1:
            out.append(vals[0])
        elif len(vals) <= 4:
            out.append(make_tuple(*vals))
        else:
            out.append(vals)
    return out


def reference_print(values, subtasks, parallelism):
    """Old ``PrintSink.emit`` per row: (stream text, retained lines)."""
    out, lines, rr = io.StringIO(), [], 0
    for j, value in enumerate(values):
        body = repr(value) if not isinstance(value, str) else value
        if not isinstance(value, str) and not hasattr(value, "_FIELDS"):
            body = _java_str(value)
        if parallelism > 1:
            st = subtasks[j] if subtasks is not None else None
            if st is None:
                st, rr = rr, (rr + 1) % parallelism
            line = f"{(st % parallelism) + 1}> {body}"
        else:
            line = body
        lines.append(line)
        print(line, file=out)
    return out.getvalue(), lines


def typed(rows):
    """Each row with the type of every field, so equal-but-differently-
    typed values (1 vs 1.0 vs True, float vs float32) differ."""
    def one(v):
        return (type(v), v)

    out = []
    for r in rows:
        if isinstance(r, (tuple, Tuple2, Tuple3, Tuple4)):
            out.append((type(r), tuple(one(v) for v in r)))
        else:
            out.append(one(r))
    return out


# -- columns -----------------------------------------------------------------

N = 37
RNG = np.random.default_rng(20261018)  # subtasks and the unit values


def _table():
    t = StringTable()
    for i in range(50):
        t.intern(f"10.8.{i}.1")
    return t


def _derived():
    t = DerivedKeyTable()
    for v in ("a", 7, 2.5, True):
        t.intern_value(v)
    return t


STR_TABLE = _table()
DERIVED = _derived()


def column(kind, n, rng):
    if kind == "str":
        ids = rng.integers(0, 50, n).astype(np.int32)
        ids[::5] = -1  # padding ids read None
        return STR, ids, STR_TABLE
    if kind == "str_dense":
        return STR, rng.integers(0, 50, n).astype(np.int32), STR_TABLE
    if kind == "derived":
        return STR, rng.integers(1, 5, n).astype(np.int32), DERIVED
    if kind == "f64":
        return F64, rng.normal(0, 1e6, n), None
    if kind == "f64_from_f32":
        return F64, rng.normal(0, 1e3, n).astype(np.float32), None
    if kind == "f64_from_i64":
        return F64, rng.integers(-2**40, 2**40, n), None
    if kind == "i64":
        return I64, rng.integers(-2**62, 2**62, n), None
    if kind == "i64_from_i32":
        return I64, rng.integers(-2**30, 2**30, n).astype(np.int32), None
    if kind == "i64_from_bool":
        return I64, rng.random(n) < 0.5, None
    if kind == "bool":
        return BOOL, rng.random(n) < 0.5, None
    if kind == "bool_from_i32":
        return BOOL, rng.integers(0, 3, n).astype(np.int32), None
    raise KeyError(kind)


LAYOUTS = {
    "1:str": ["str"],
    "1:f64": ["f64"],
    "1:i64": ["i64"],
    "1:bool": ["bool"],
    "2:str,f64": ["str_dense", "f64"],
    "2:derived,i64": ["derived", "i64_from_i32"],
    "3:str,str,f64": ["str", "str_dense", "f64_from_f32"],
    "3:i64,bool,f64": ["i64_from_bool", "bool_from_i32", "f64_from_i64"],
    "4:str,i64,f64,bool": ["str", "i64", "f64", "bool"],
    "5:i64,i64,str,f64,str": ["i64", "i64", "str", "f64", "str_dense"],
}


def rows_of(kinds, tables, cols):
    return list(iter_rows(EmissionFormatter(kinds, tables).columns(cols)))


def layout(name, n=N):
    rng = np.random.default_rng([n] + [ord(c) for c in name])
    kinds, cols, tables = [], [], []
    for k in LAYOUTS[name]:
        kind, col, table = column(k, n, rng)
        kinds.append(kind)
        cols.append(col)
        tables.append(table)
    return kinds, cols, tables


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_formatter_rows_match_row_by_row(name):
    kinds, cols, tables = layout(name)
    got = rows_of(kinds, tables, cols)
    want = reference_rows(kinds, tables, cols)
    assert len(got) == N
    assert typed(got) == typed(want)


@pytest.mark.parametrize("name", ["1:str", "2:str,f64", "5:i64,i64,str,f64,str"])
def test_formatter_empty_emission(name):
    kinds, cols, tables = layout(name, n=0)
    assert rows_of(kinds, tables, cols) == []
    assert rows_of([], [], []) == []


def test_string_table_gather_reads_none_for_padding():
    ids = [3, -1, 0, 49]
    assert STR_TABLE.lookup_many(ids) == [
        STR_TABLE.lookup(3), None, STR_TABLE.lookup(0), STR_TABLE.lookup(49)
    ]
    assert DERIVED.lookup_many([1, 2, 3, 4]) == ["a", 7, 2.5, True]


# -- the tuples the formatter builds -----------------------------------------

VALUES = {2: ("10.8.22.1", 80.5), 3: (7, "cpu0", 99.9),
          4: ("k", 1, 2.5, True)}
CLASSES = {2: Tuple2, 3: Tuple3, 4: Tuple4}


@pytest.mark.parametrize("arity", [2, 3, 4])
def test_trusted_tuple_is_the_tuple(arity):
    cls, vals = CLASSES[arity], VALUES[arity]
    fast, slow = cls.trusted(*vals), cls(*vals)
    assert type(fast) is cls and isinstance(fast, cls)
    assert fast == slow and hash(fast) == hash(slow)
    assert repr(fast) == repr(slow)
    assert [fast[i] for i in range(arity)] == list(vals)
    assert [getattr(fast, f"f{i}") for i in range(arity)] == list(vals)
    assert fast.__dict__ == slow.__dict__
    for proto in (0, 2, pickle.HIGHEST_PROTOCOL):
        back = pickle.loads(pickle.dumps(fast, protocol=proto))
        assert type(back) is cls and back == slow
        assert back.__dict__ == slow.__dict__
    fast[0] = "x"
    assert fast.f0 == "x"


# -- sinks: one hand-off, the same output ------------------------------------


def values_of(name, n=N):
    kinds, cols, tables = layout(name, n)
    return rows_of(kinds, tables, cols)


@pytest.mark.parametrize("parallelism", [1, 4])
@pytest.mark.parametrize("with_subtasks", [False, True])
@pytest.mark.parametrize("name", ["1:f64", "1:str", "2:str,f64",
                                  "4:str,i64,f64,bool",
                                  "5:i64,i64,str,f64,str"])
def test_print_sink_prints_byte_for_byte(name, parallelism, with_subtasks):
    values = values_of(name)
    subtasks = (RNG.integers(0, 8, N).tolist() if with_subtasks else None)
    want_text, want_lines = reference_print(values, subtasks, parallelism)
    out = io.StringIO()
    sink = PrintSink(parallelism=parallelism, stream=out)
    # two hand-offs: the round-robin carries over from one to the next
    half = N // 2
    assert sink.emit_many(iter(values[:half]),
                          None if subtasks is None else subtasks[:half]) == half
    assert sink.emit_many(iter([]), None if subtasks is None else []) == 0
    assert sink.emit_many(iter(values[half:]),
                          None if subtasks is None else subtasks[half:]) == (
        N - half)
    assert out.getvalue() == want_text
    assert sink.lines == want_lines
    # and row by row
    out1 = io.StringIO()
    one = PrintSink(parallelism=parallelism, stream=out1)
    for j, v in enumerate(values):
        one.emit(v, subtask=None if subtasks is None else subtasks[j])
    assert out1.getvalue() == want_text


class Count:
    def __init__(self):
        self.value = 0
        self.calls = 0

    def inc(self, n=1):
        self.value += n
        self.calls += 1


def test_collect_and_fn_sinks_take_a_step_in_one_count():
    values = values_of("2:str,f64")

    class Handle:
        def __init__(self):
            self.items = []

        def append(self, v):
            self.items.append(v)

    h = Handle()
    collect = CollectSink(h)
    collect.obs_counter = Count()
    assert collect.emit_many(iter(values)) == N
    assert h.items == values and collect.obs_counter.value == N
    assert collect.obs_counter.calls == 1

    got = []
    fn = FnSink(got.append)
    fn.obs_counter = Count()
    assert fn.emit_many(iter(values), subtasks=list(range(N))) == N
    assert fn.emit_many(iter([])) == 0
    assert got == values and fn.obs_counter.value == N
    assert fn.obs_counter.calls == 2
    assert not collect.reads_subtask and not fn.reads_subtask
    assert PrintSink(parallelism=4).reads_subtask
    assert not PrintSink(parallelism=1).reads_subtask


def test_retrying_sink_retries_each_row_on_its_own():
    """Under an injected ``sink_emit`` fault every row still meets the
    fault hook once per attempt, as with one ``emit`` per row."""
    values = values_of("2:str,f64", n=9)

    def run(hand_off):
        inj = FaultInjector(FaultPoint("sink_emit", at=3),
                            FaultPoint("sink_emit", at=7))
        got = []
        sink = RetryingSink(FnSink(got.append), attempts=2, base_ms=0.0,
                            fault=inj.check)
        sink.retry_counter = Count()
        hand_off(sink)
        return got, list(inj.log), sink.retry_counter.value

    many = run(lambda s: s.emit_many(iter(values)))
    per_row = run(lambda s: [s.emit(v) for v in values])
    assert many == per_row
    assert many[0] == values and many[2] == 2
    out = io.StringIO()
    retrying_print = RetryingSink(PrintSink(parallelism=4, stream=out))
    assert retrying_print.reads_subtask
    retrying_print.emit_many(values, [1, 2, 3, 0, 1, 2, 3, 0, 1])
    assert out.getvalue() == reference_print(
        values, [1, 2, 3, 0, 1, 2, 3, 0, 1], 4)[0]


def test_branch_ops_over_many_rows_match_one_by_one():
    values = values_of("2:str,f64")
    ops = [("filter", lambda t: t.f1 > 0), ("map", lambda t: Tuple2(t.f0, -t.f1)),
           ("filter", lambda t: t.f0 is not None)]
    subtasks = list(range(N))
    items, sts = _apply_ops_many(ops, values, subtasks)
    want, want_sts = [], []
    for v, st in zip(values, subtasks):
        item, keep = _apply_ops(ops, v)
        if keep:
            want.append(item)
            want_sts.append(st)
    assert items == want and sts == want_sts
    assert _apply_ops_many(ops, values)[1] is None


# -- whole jobs: the batched hand-off against the per-row fan-out ------------

BATCHED = dict(enabled=True, ledger=False)
PER_ROW = dict(enabled=True)  # the ledger (auto-on) keeps the per-row path


def series_total(res, name):
    series = res.metrics.obs_snapshot()["metrics"]["series"]
    return sum(s["value"] for s in series if s["name"].endswith(name))


def buffers(n_buf=10, lines=24, channels=7):
    """``ts channel flowBytes`` blocks 20 s of event time apart, so the
    chapter-3 windows fire while the stream runs."""
    out = []
    t0 = np.datetime64("2019-08-28T09:00:00")
    for b in range(n_buf):
        rows = []
        for i in range(lines):
            ts = t0 + np.timedelta64(20 * b + i % 20, "s")
            rows.append(f"{ts} c{(b + i) % channels} {100 + 7 * i}")
        out.append(("\n".join(rows).encode() + b"\n", len(rows)))
    return out


def run_eventtime(obs, sinks, **over):
    """The chapter-3 event-time job; ``sinks(stream)`` attaches the
    branches and returns what to read back after the run."""
    cfg = StreamConfig(batch_size=32, key_capacity=64, alert_capacity=256,
                       obs=ObsConfig(**obs), **over)
    env = StreamExecutionEnvironment(cfg)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    read = sinks(build(env, env.add_source(ReplayBytesSource(buffers()))))
    res = env.execute("emit-columnar")
    return read(), res


@pytest.mark.parametrize("parallelism", [1, 4])
def test_job_print_output_is_byte_identical(parallelism, capsys):
    """``print()`` on the event-time job, at parallelism 1 (no prefix)
    and 4 (the sharded ``subtask`` column names each ``n>``)."""
    outs = {}
    for mode, obs in (("batched", BATCHED), ("per_row", PER_ROW)):
        capsys.readouterr()
        _, res = run_eventtime(obs, lambda s: (s.print(), lambda: None)[1],
                               parallelism=parallelism)
        text = capsys.readouterr().out
        outs[mode] = (text, series_total(res, "emit_rows_batched_total"),
                      res.metrics.records_emitted)
    text, batched, emitted = outs["batched"]
    assert text == outs["per_row"][0]
    lines = text.splitlines()
    assert len(lines) == emitted > 0
    assert batched == emitted and outs["per_row"][1] == 0
    if parallelism > 1:
        prefixes = {ln.split(">", 1)[0] for ln in lines}
        assert len(prefixes) > 1 and prefixes <= {"1", "2", "3", "4"}
    else:
        assert all(ln.startswith("(") for ln in lines)


def test_job_branches_with_map_and_filter():
    """Two branches off the window's output, one with its own filter
    and map: each sink receives the same rows, typed and ordered, and
    the batched count is what the two sinks received."""
    def sinks(s):
        everything = s.collect()
        high = s.filter(lambda t: t.f1 > 3.5e-5).map(
            lambda t: Tuple3(t.f0, t.f1, t.f1 > 4.5e-5)).collect()
        return lambda: (everything.items, high.items)

    (all_b, high_b), res_b = run_eventtime(BATCHED, sinks)
    (all_r, high_r), res_r = run_eventtime(PER_ROW, sinks)
    assert typed(all_b) == typed(all_r) and typed(high_b) == typed(high_r)
    want = [Tuple3(t.f0, t.f1, t.f1 > 4.5e-5) for t in all_b
            if t.f1 > 3.5e-5]
    assert typed(high_b) == typed(want)
    assert all_b and high_b and len(high_b) < len(all_b)
    assert series_total(res_b, "emit_rows_batched_total") == (
        len(all_b) + len(high_b))
    assert series_total(res_r, "emit_rows_batched_total") == 0


def test_job_retrying_sink_under_injected_fault():
    """``sink_retries`` absorbs two injected ``sink_emit`` faults: the
    same retries, at the same rows, and the same rows in the sink."""
    def run(obs):
        inj = FaultInjector(FaultPoint("sink_emit", at=4),
                            FaultPoint("sink_emit", at=11))
        cfg = inj.install(StreamConfig(
            batch_size=32, key_capacity=64, alert_capacity=256,
            sink_retries=2, sink_retry_base_ms=0.0, obs=ObsConfig(**obs)))
        env = StreamExecutionEnvironment(cfg)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        got = []
        build(env, env.add_source(ReplayBytesSource(buffers()))).add_sink(
            got.append)
        res = env.execute("emit-columnar-retry")
        return got, list(inj.log), series_total(res, "sink0_retries"), res

    got_b, log_b, retries_b, res_b = run(BATCHED)
    got_r, log_r, retries_r, _ = run(PER_ROW)
    assert typed(got_b) == typed(got_r) and got_b
    assert log_b == log_r and len(log_b) == 2
    assert retries_b == retries_r == 2
    assert series_total(res_b, "emit_rows_batched_total") == len(got_b)


def test_job_ledger_accounts_match_the_batched_contents():
    """With the ledger on, the sink's account folds the same rows, in
    the same order, as the batched run collects: its digest re-derives
    from the batched contents, and every edge balances."""
    got_b, _ = run_eventtime(BATCHED, lambda s: (lambda h: lambda: h.items)(
        s.collect()))
    got_r, res = run_eventtime(PER_ROW, lambda s: (lambda h: lambda: h.items)(
        s.collect()))
    assert typed(got_b) == typed(got_r) and got_b
    state = res.metrics.job_obs.ledger.state()
    h = hashlib.sha256()
    for row in got_b:
        h.update(encode_row(row))
    anchor = state["anchors"]["sink0"]
    assert anchor["count"] == len(got_b)
    assert anchor["digest"] == h.hexdigest()
    assert state["violations"]["total"] == 0
    assert all(e["residual"] == 0 for e in state["edges"]
               if e.get("residual") is not None)


def test_job_rows_are_built_as_the_sink_takes_them():
    """No step's rows are all alive at once: each row is gone by the
    time the sink takes the next (a list of a fire's ~10k rows would
    hand Python's cyclic collector ~10k objects to promote a fire)."""
    held = []

    def sinks(s):
        prev = [None]

        def take(t):
            held.append(prev[0] is not None and prev[0]() is not None)
            prev[0] = weakref.ref(t)
        s.add_sink(take)
        return lambda: None

    _, res = run_eventtime(BATCHED, sinks)
    assert len(held) == res.metrics.records_emitted > 0
    assert not any(held)


class SecondsExtractor(BoundedOutOfOrdernessTimestampExtractor):
    def __init__(self):
        super().__init__(Time.seconds(0))

    def extract_timestamp(self, line):
        return int(line.split(" ")[0]) * 1000


def test_job_late_side_output_rows_match():
    """Records later than the window's end reach the late side output
    through the same formatter and hand-off."""
    lines = ["10 a 1", "20 b 2", "70 a 3", "15 a 4", "25 b 5", "130 b 6",
             "65 c 7", "59 a 8"]

    def parse(line):
        p = line.split(" ")
        return Tuple3(int(p[0]), p[1], int(p[2]))

    def run(obs):
        env = StreamExecutionEnvironment(StreamConfig(
            batch_size=1, key_capacity=16, obs=ObsConfig(**obs)))
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        tag = OutputTag("late")
        summed = (
            env.add_source(ReplaySource(lines))
            .assign_timestamps_and_watermarks(SecondsExtractor())
            .map(parse).key_by(1)
            .window(TumblingEventTimeWindows.of(Time.seconds(60)))
            .side_output_late_data(tag)
            .reduce(lambda a, b: Tuple3(a.f0, a.f1, a.f2 + b.f2))
        )
        main = summed.collect()
        late = summed.get_side_output(tag).collect()
        res = env.execute("emit-columnar-late")
        return main.items, late.items, res

    main_b, late_b, res_b = run(BATCHED)
    main_r, late_r, _ = run(PER_ROW)
    assert typed(main_b) == typed(main_r) and typed(late_b) == typed(late_r)
    assert late_b and main_b
    assert series_total(res_b, "emit_rows_batched_total") == (
        len(main_b) + len(late_b))
