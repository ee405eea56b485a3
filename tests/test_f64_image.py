"""Order-only float64 values ride the device as their exact int64 image.

A rolling ``max``/``min``/``max_by``/``min_by`` field and a rolling
stage's keep-first float64 fields are only compared, selected or passed
through on the device, so they cross the wire, live in state and come
back as ``records.f64_image`` (``RollingProgram.image_fields``). The
system is held here to a record-at-a-time reference of Flink's rolling
semantics in Java's ``Double.compare`` order, bit for bit (``float.hex``
equality), at ``parallelism`` 1 and sharded over the CPU devices; the
wire and the state are checked to carry no such value as float64, and
the bandwidth job's and the running max's step programs are pinned by
the sha256 of their text.
"""

import hashlib
import math
import struct

import jax
import numpy as np
import pytest

from tpustream import StreamExecutionEnvironment, TimeCharacteristic, Tuple4
from tpustream.config import ObsConfig, StreamConfig
from tpustream.javacompat import Double
from tpustream.records import f64_from_image, f64_image
from tpustream.runtime import executor
from tpustream.runtime.sources import ReplayBytesSource

KINDS = ("max", "min", "max_by", "min_by")

# doubles a float32 pair cannot hold, the extremes of the exponent
# range, subnormals, signed zeros, infinities and NaN
SPECIAL = [57.3, 0.1, 1 / 3, -1 / 3, 2.0 ** 1000 * 1.1, -(2.0 ** 1000) * 1.3,
           2.0 ** -1000 * 1.7, 5e-324, -5e-324, 2.2e-308, 0.0, -0.0,
           math.inf, -math.inf, math.nan, 1.0000000000000002, 99.9, 10.0]


# -- the reference: Flink's rolling aggregates, one record at a time ----------

def _long_bits(x: float) -> int:
    """Java's ``Double.doubleToLongBits``: NaN as the canonical NaN."""
    if x != x:
        return 0x7FF8000000000000
    return struct.unpack("<q", struct.pack("<d", x))[0]


def java_compare(a: float, b: float) -> int:
    """Java's ``Double.compare`` (the order ``Double.compareTo`` and so
    Flink's ``ComparableAggregator`` use)."""
    if a < b:
        return -1
    if a > b:
        return 1
    x, y = _long_bits(a), _long_bits(b)
    return 0 if x == y else (-1 if x < y else 1)


def flink_rolling(records, kind: str, pos: int, key_pos: int = 0) -> list:
    """Every record emits its key's aggregate. ``max``/``min`` keep the
    key's first record and replace only field ``pos`` unless the kept
    value is strictly more extreme (MaxComparator / MinComparator);
    ``max_by``/``min_by`` keep the whole record that is strictly more
    extreme, the first one on ties."""
    state, out = {}, []
    for r in records:
        k = r[key_pos]
        cur = state.get(k)
        if cur is None:
            cur = state[k] = list(r)
        else:
            c = java_compare(cur[pos], r[pos])
            if kind == "max" and not c > 0:
                cur[pos] = r[pos]
            elif kind == "min" and not c < 0:
                cur[pos] = r[pos]
            elif kind == "max_by" and c < 0:
                cur = state[k] = list(r)
            elif kind == "min_by" and c > 0:
                cur = state[k] = list(r)
        out.append(tuple(cur))
    return out


# -- the system -------------------------------------------------------------


def parse(line: str) -> Tuple4:
    items = line.split(" ")
    return Tuple4(items[0], items[1], Double.parseDouble(items[2]),
                  Double.parseDouble(items[3]))


def make_lines(seed: int, n: int, keys: int = 5) -> list:
    """``host cpu first value`` lines: random doubles that float32
    cannot hold, mixed with the special values."""
    rng = np.random.default_rng(seed)
    pool = SPECIAL + rng.uniform(-1e6, 1e6, 24).tolist() + (
        rng.integers(100, 1000, 12) / 10.0).tolist()
    out = []
    for i in range(n):
        a, b = rng.integers(0, len(pool), 2)
        out.append(f"h{rng.integers(0, keys)} cpu{i % 4} "
                   f"{pool[a]!r} {pool[b]!r}")
    return out


def run(lines, kind, parallelism=1, batch_size=8, pos=3, **cfg):
    env = StreamExecutionEnvironment(StreamConfig(
        batch_size=batch_size, key_capacity=64, parallelism=parallelism,
        **cfg))
    rows = []
    keyed = env.from_collection(lines).map(parse).key_by(0)
    getattr(keyed, kind)(pos).add_sink(lambda t: rows.append(tuple(t)))
    res = env.execute("f64-image")
    return rows, res


def hexes(rows) -> list:
    return [tuple(v.hex() if isinstance(v, float) else v for v in r)
            for r in rows]


def want(lines, kind, pos=3):
    recs = [tuple(parse(line)) for line in lines]
    return hexes(flink_rolling(recs, kind, pos))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("parallelism", [1, 4])
def test_rolling_matches_java_order_bit_for_bit(kind, parallelism):
    lines = make_lines(7 + parallelism, 64)
    got, _ = run(lines, kind, parallelism=parallelism)
    if parallelism > 1:
        # each subtask emits in arrival order; compare per key
        by_key = lambda rows: sorted(rows, key=lambda r: r[0])  # noqa: E731
        assert by_key(hexes(got)) == by_key(want(lines, kind))
    else:
        assert hexes(got) == want(lines, kind)


@pytest.mark.parametrize("kind", ("max", "min"))
def test_signed_zeros_and_nan_in_java_order(kind):
    lines = [f"h0 cpu0 1.5 {v!r}" for v in
             (-0.0, 0.0, -0.0, math.nan, math.inf, -math.inf, 0.0)]
    got, _ = run(lines, kind, batch_size=4)
    assert hexes(got) == want(lines, kind)
    # max: 0.0 beats -0.0 and NaN beats +inf; min: -0.0 beats 0.0
    last = got[-1][3]
    if kind == "max":
        assert math.isnan(last)
    else:
        assert last == -math.inf


def test_keep_first_f64_fields_of_sum_stay_exact():
    # sum's aggregated field keeps the float64 arithmetic path; the
    # float64 field it passes through rides as its image
    lines = make_lines(3, 40)
    got, _ = run(lines, "sum", pos=3)
    recs = [tuple(parse(line)) for line in lines]
    first = {}
    for r, g in zip(recs, got):
        first.setdefault(r[0], r[2])
        assert g[2].hex() == first[r[0]].hex()


def test_image_is_the_java_order_and_inverts_exactly():
    vals = np.array(SPECIAL + [-math.nan], dtype=np.float64)
    img = f64_image(vals)
    for i, a in enumerate(vals):
        for j, b in enumerate(vals):
            c = java_compare(float(a), float(b))
            assert int(img[i] > img[j]) - int(img[i] < img[j]) == c, (a, b)
    back = f64_from_image(img)
    for a, b in zip(vals, back):
        assert float(a).hex() == float(b).hex()


# -- structural guards --------------------------------------------------------


@pytest.fixture
def captured(monkeypatch):
    """The runners that packed a batch, and each packed wire tuple."""
    seen = {"runners": [], "wire": []}
    orig = executor.Runner._pack

    def pack(self, cols, valid, ts):
        out = orig(self, cols, valid, ts)
        if self not in seen["runners"]:
            seen["runners"].append(self)
        seen["wire"].append(out[0])
        return out

    monkeypatch.setattr(executor.Runner, "_pack", pack)
    return seen


@pytest.mark.parametrize("kind", KINDS)
def test_no_order_only_f64_reaches_the_device(captured, kind):
    got, res = run(make_lines(5, 24), kind,
                   obs=ObsConfig(enabled=True))
    (runner,) = captured["runners"]
    assert runner.program.image_fields == (2, 3)
    assert runner._col_modes[2:] == ("img", "img")
    for wire in captured["wire"]:
        assert all(w.dtype != np.float64 for w in wire)
    planes = runner.state["planes"]
    assert all(p.dtype != np.float64 for p in planes)
    # host id (int32), cpu (int32), two image planes each
    assert [str(p.dtype) for p in planes] == ["int32"] * 6
    # the HBM accounting holds the image planes with the rolling state
    # (64 keys: the seen bitmap and six int32 planes)
    assert runner._state_mem.component_bytes()["rolling_planes"] == (
        64 + 6 * 64 * 4)
    total = sum(s["value"] for s in res.metrics.obs_snapshot()["metrics"][
        "series"] if s["name"].endswith("f64_ordered_values_total"))
    assert total == 2 * len(got)


def test_float32_override_keeps_its_32bit_plane(captured):
    # the lossy opt-in narrows the aggregated field to float32 and keeps
    # it on today's path; the pass-through float64 still rides as image
    run(make_lines(5, 24), "max", acc_dtype="float32",
        value_dtype="float32")
    (runner,) = captured["runners"]
    assert runner.program.image_fields == (2,)
    dtypes = [str(p.dtype) for p in runner.state["planes"]]
    assert dtypes == ["int32", "int32", "int32", "int32", "float32"]


def _step_hlo(captured_runs, build, text: bytes, n: int,
              event_time: bool = False):
    """StableHLO text of a one-chip job's jitted step, built by
    ``build`` over ``n`` lines of ``text``."""
    env = StreamExecutionEnvironment(StreamConfig(
        batch_size=32, key_capacity=64, alert_capacity=256,
        obs=ObsConfig(enabled=True)))
    if event_time:
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    build(env, env.add_source(ReplayBytesSource([(text, n)]))).collect()
    res = env.execute("step-hlo")
    runner = captured_runs["runners"][0]
    shape = lambda x: jax.ShapeDtypeStruct(np.shape(x),  # noqa: E731
                                           np.asarray(x).dtype)
    inputs = jax.tree_util.tree_map(shape, runner._empty_inputs())
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), runner.state)
    step = executor.Runner._counted_step(_NoObs(runner), runner._inner_step)
    hlo = step.lower(state, *inputs, shape(np.int64(0))).as_text()
    return hlo, runner, res


def _bandwidth_step_hlo(captured_runs):
    """StableHLO text of the one-chip bandwidth job's jitted step."""
    from tpustream.jobs.chapter3_bandwidth_eventtime import build

    t0 = np.datetime64("2019-08-28T09:00:00")
    text = "\n".join(f"{t0 + np.timedelta64(i, 's')} c{i % 5} {100 + i}"
                     for i in range(64)).encode() + b"\n"
    return _step_hlo(captured_runs, build, text, 64, event_time=True)


def _rolling_step_hlo(captured_runs):
    """StableHLO text of the one-chip running max's (chapter 2,
    ``cpu-max-1M``'s job) jitted step."""
    from tpustream.jobs.chapter2_max import build

    text = "\n".join(f"{1565000000 + i} host{i % 7:07d} cpu{i % 4} "
                     f"{10 + i % 90}.{i % 10}" for i in range(64)).encode()
    return _step_hlo(captured_runs, build, text + b"\n", 64)


class _NoObs:
    """The runner as ``_counted_step`` reads it, with no compile
    instrumentation around the step."""

    def __init__(self, runner):
        self._r = runner
        self._compile_obs = None

    def __getattr__(self, name):
        return getattr(self._r, name)


# sha256 of the one-chip bandwidth step's StableHLO text above: the
# images must leave that program as it is (update only with a deliberate
# change to the window program or the executor's step wrapper)
BANDWIDTH_STEP_SHA256 = (
    "e61e2be0f706f049a26247929c30c62db14284c99d2b55c76eda037885163994")

# sha256 of the one-chip running max's step (the rolling program, which
# runs no pane ring): a change to the pane ring must leave it as it is
ROLLING_STEP_SHA256 = (
    "5d2ccf5df78bd981d568f91802b680a7fcebb5d2e2ea23c03274729894b50d75")


def test_bandwidth_step_program_is_unchanged(captured):
    hlo, runner, res = _bandwidth_step_hlo(captured)
    assert runner.program.image_fields == ()
    assert "img" not in runner._col_modes
    digest = hashlib.sha256(hlo.encode()).hexdigest()
    assert digest == BANDWIDTH_STEP_SHA256
    series = res.metrics.obs_snapshot()["metrics"]["series"]
    counts = [s["value"] for s in series
              if s["name"].endswith("f64_ordered_values_total")]
    assert counts and sum(counts) == 0


def test_rolling_step_program_is_unchanged(captured):
    hlo, runner, _ = _rolling_step_hlo(captured)
    assert runner.program.image_fields == (2,)
    assert hashlib.sha256(hlo.encode()).hexdigest() == ROLLING_STEP_SHA256


# -- checkpoints: the v12 goldens hold the max in a float64 plane -------------


def _golden_run(tmp_path, lines, restore=None):
    from tpustream.jobs.chapter2_max import build

    env = StreamExecutionEnvironment(StreamConfig(
        batch_size=2, checkpoint_dir=str(tmp_path),
        checkpoint_interval_batches=1, checkpoint_async=False))
    rows = []
    build(env, env.from_collection(lines)).add_sink(
        lambda t: rows.append(tuple(t)))
    if restore is not None:
        env.restore_from_checkpoint(restore)
    captured = []
    orig = executor.Runner._pack

    def pack(self, *a):
        if self not in captured:
            captured.append(self)
        return orig(self, *a)

    executor.Runner._pack = pack
    try:
        env.execute("golden-continue")
    finally:
        executor.Runner._pack = orig
    return rows, captured


@pytest.mark.parametrize("golden", ["ckpt-fv12.npz", "ckpt-fv12m.npz",
                                    "ckpt-fv13.npz"])
def test_v12_golden_restores_and_continues_bit_for_bit(tmp_path, golden):
    """The golden snapshots (the chapter-2 max with its v12 float64
    plane; fv13 is that payload under this build's number) restore by
    migrating the plane to the image, and the job continues with the
    rows an uninterrupted run gives."""
    import importlib.util
    import os

    here = os.path.join(os.path.dirname(__file__), "goldens")
    spec = importlib.util.spec_from_file_location(
        "make_checkpoint_goldens",
        os.path.join(here, "make_checkpoint_goldens.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    more = [f"15634521{i:02d} 10.8.22.{i % 4} cpu{i % 2} {(i * 13) % 100}.25"
            for i in range(14)]
    lines = mod.LINES + more
    full, _ = _golden_run(tmp_path / "full", lines)
    got, runners = _golden_run(tmp_path / "resumed", lines,
                               restore=os.path.join(here, golden))
    assert hexes(got) == hexes(full[len(mod.LINES):])
    (runner,) = runners
    assert [str(p.dtype) for p in runner.state["planes"]] == ["int32"] * 4
