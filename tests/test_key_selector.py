"""keyBy(KeySelector): Flink's surface accepts a key function, not just
a field index (VERDICT r2 missing #5). Field-projecting selectors — the
practical usage — resolve to field indices at plan time via a sentinel
probe (runtime/plan.py resolve_key_selector); selectors COMPUTING a
derived key (VERDICT r3 next #6) fall back to host evaluation per
record, interned into a synthetic key column that user functions and
emissions never see.
"""

import pytest

from tpustream import KeySelector, StreamExecutionEnvironment, Tuple2
from tpustream.config import StreamConfig
from tpustream.runtime.plan import resolve_key_selector
from tpustream.runtime.sources import ReplaySource


def parse(line):
    p = line.split(" ")
    return Tuple2(p[0], float(p[1]))


LINES = ["a 1", "b 10", "a 2", "b 20", "a 4"]


def run(key, parallelism=0, lines=LINES, **cfg):
    cfg.setdefault("batch_size", 2)
    cfg.setdefault("key_capacity", 16)
    if parallelism:
        cfg.update(parallelism=parallelism, print_parallelism=1)
    env = StreamExecutionEnvironment(StreamConfig(**cfg))
    text = env.add_source(ReplaySource(lines))
    h = (
        text.map(parse)
        .key_by(key)
        .reduce(lambda a, b: Tuple2(a.f0, a.f1 + b.f1))
        .collect()
    )
    env.execute("selector")
    return [(t.f0, t.f1) for t in h.items]


def test_lambda_selector_matches_field_index():
    assert run(lambda r: r.f0) == run(0)


def test_key_selector_class():
    class ByHost(KeySelector):
        def get_key(self, value):
            return value.f0

    assert run(ByHost()) == run(0)


def test_key_selector_camel_case_override():
    # Flink-style subclass overriding ONLY getKey (the advertised alias)
    class ByHost(KeySelector):
        def getKey(self, value):
            return value.f0

    assert run(ByHost()) == run(0)


def test_getitem_selector():
    assert run(lambda r: r[0]) == run(0)


def test_resolver_units():
    assert resolve_key_selector(1) == 1
    assert resolve_key_selector(lambda r: r.f2) == 2
    assert resolve_key_selector(lambda r: r[3]) == 3


def test_resolver_rejects_computed_selector():
    # the RESOLVER still refuses (no field to project); the planner
    # catches this and routes to the host-evaluated fallback
    with pytest.raises(NotImplementedError, match="computed"):
        resolve_key_selector(lambda r: str(r.f0) + "x")


def test_bool_key_rejected():
    # bool subclasses int: key_by(True) must not silently key on field 1
    with pytest.raises(NotImplementedError):
        resolve_key_selector(True)


def test_branching_selectors_classify_as_computed():
    """A selector that BRANCHES on a field (truthiness / ordering /
    equality / membership) computes a key; the plan-time probe must not
    misread it as a pure projection (ADVICE r4: probe truthiness used
    to classify ``r.f1 or 'default'`` as ('pos', 1), silently keying
    every record on f1)."""
    from tpustream.runtime.plan import classify_key_selector

    branching = [
        lambda r: r.f1 or "default",                    # __bool__
        lambda r: r.f1 if r.f2 > 0 else "low",          # ordering
        lambda r: "special" if r.f0 == "alert" else r.f1,  # __eq__
        lambda r: "x" if r.f0 in {"a", "b"} else r.f1,  # set: __hash__
        lambda r: "x" if r.f0 in ("a", "b") else r.f1,  # tuple: __eq__
    ]
    for fn in branching:
        kind, _ = classify_key_selector(fn)
        assert kind == "computed", fn
    # pure projections still resolve symbolically
    assert classify_key_selector(lambda r: r.f1) == ("pos", 1)


def test_branching_selector_end_to_end():
    # the __bool__-guard path, run on data: r.f0 or 'default' groups
    # falsy keys ('' after strip-to-empty is impossible here, so use a
    # branch on the value field instead)
    lines = ["a 1", "b 95", "a 2", "b 96"]
    got = run(lambda r: r.f0 if r.f1 > 90 else "low", lines=lines)
    # keys: low(a1), b(95), low(a1+2), b(95+96)
    assert got == [("a", 1.0), ("b", 95.0), ("a", 3.0), ("b", 191.0)]


def test_derived_key_table_reserves_placeholder():
    """DerivedKeyTable id 0 is a dead slot (ADVICE r4): filter-dropped
    rows carry it, so even a host/device filter disagreement cannot
    alias the first REAL derived key's state."""
    from tpustream.records import DerivedKeyTable

    t = DerivedKeyTable()
    assert len(t) == 1                      # placeholder pre-interned
    assert t.intern_value("a") == 1         # real keys start at 1
    assert t.lookup(1) == "a"
    # round-trips through checkpoint state
    t2 = DerivedKeyTable()
    t2.load_state_dict(t.state_dict())
    assert t2.intern_value("a") == 1 and t2.lookup(1) == "a"


def test_old_format_checkpoint_rejected(tmp_path):
    """A snapshot written by a different FORMAT_VERSION must fail with
    the explicit version message, not a downstream leaf-shape error
    (ADVICE r4: v6 builds vs v7 grown-capacity snapshots)."""
    import json

    import numpy as np

    from tpustream.runtime.checkpoint import OLDEST_READABLE, load_checkpoint

    meta = {
        "version": OLDEST_READABLE - 1,
        "record_kinds": [], "tables": [], "source_pos": 0,
        "proc_now": 0, "emitted": 0, "batches": 1,
    }
    p = tmp_path / "ckpt-0000000001.npz"
    np.savez(p, __meta__=np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8))
    with pytest.raises(ValueError, match="version"):
        load_checkpoint(str(p))


# ---------------------------------------------------------------------------
# computed (derived-key) selectors: host-evaluated fallback
# ---------------------------------------------------------------------------

def test_computed_selector_matches_projection_groups():
    # str(r.f0) + "x" derives a key BIJECTIVE with f0: groups (and the
    # visible output records) must match keying on the field itself
    assert run(lambda r: str(r.f0) + "x") == run(0)


def test_computed_selector_coarser_groups():
    lines = ["a 1", "b 10", "c 100", "aa 2", "bb 20", "cc 200"]
    got = run(lambda r: len(r.f0), lines=lines)
    # keys: 1 -> a,b,c ; 2 -> aa,bb,cc — rolling sums with Flink's
    # stale-field record semantics (first record's f0 is kept)
    assert got == [
        ("a", 1.0), ("a", 11.0), ("a", 111.0),
        ("aa", 2.0), ("aa", 22.0), ("aa", 222.0),
    ]


def test_computed_selector_sharded():
    lines = [f"h{i % 5} {i}" for i in range(24)]
    single = run(lambda r: len(r.f0) + hash(r.f0) % 7, lines=lines,
                 batch_size=8)
    sharded = run(lambda r: len(r.f0) + hash(r.f0) % 7, lines=lines,
                  parallelism=4, batch_size=8, key_capacity=64)
    assert sorted(single) == sorted(sharded)


def test_computed_selector_process_window_gets_original_key():
    """The user process fn must receive the TRUE derived key (here an
    int), not a stringified form, and elements without any synthetic
    field."""
    from tpustream import (
        BoundedOutOfOrdernessTimestampExtractor,
        Time,
        TimeCharacteristic,
    )

    class Ts(BoundedOutOfOrdernessTimestampExtractor):
        def __init__(self):
            super().__init__(Time.milliseconds(1000))

        def extract_timestamp(self, value):
            return int(value.split(" ")[0])

    seen = []

    def probe(key, ctx, elements, out):
        seen.append((key, [tuple(e) if hasattr(e, "f0") else e for e in elements]))
        out.collect(Tuple2(str(key), float(len(list(elements)))))

    env = StreamExecutionEnvironment(StreamConfig(batch_size=2, key_capacity=16))
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    lines = ["1000 a 1", "2000 bb 2", "3000 c 3", "12000 dd 4"]
    text = env.add_source(ReplaySource(lines))
    h = (
        text.assign_timestamps_and_watermarks(Ts())
        .map(lambda l: Tuple2(l.split(" ")[1], int(l.split(" ")[2])))
        .key_by(lambda r: len(r.f0))        # derived int key: 1 or 2
        .time_window(Time.seconds(10))
        .process(probe)
        .collect()
    )
    env.execute("computed-process")
    # fires: key 1 = [0,10s) (a, c); key 2 = [0,10s) (bb) + [10,20s) (dd)
    keys = sorted(k for k, _ in seen)
    assert keys == [1, 2, 2], keys
    assert all(isinstance(k, int) for k, _ in seen)
    # elements are the visible 2-field records
    assert all(len(e) == 2 for _, els in seen for e in els)


def test_computed_selector_with_pre_filter_scalar_records():
    """A device filter between the parse map and a computed key_by must
    see the bare visible record — never the synthetic key column
    (regression: scalar-record filters crashed with Tuple2 vs int)."""
    env = StreamExecutionEnvironment(
        StreamConfig(batch_size=2, key_capacity=16)
    )
    text = env.add_source(ReplaySource(["1", "2", "3", "4", "5"]))
    h = (
        text.map(lambda l: int(l))
        .filter(lambda v: v > 1)
        .key_by(lambda v: v % 2)
        .reduce(lambda a, b: a + b)
        .collect()
    )
    env.execute("filter-computed")
    # rolling sums of 2,3,4,5 grouped by parity
    assert h.items == [2, 3, 6, 8]


def test_partial_computed_selector_never_sees_filtered_records():
    """Flink's getKey never receives a filtered-out record: a PARTIAL
    selector (here dividing by a field a filter guards) must not crash
    on rows the filter drops, and dropped rows must not intern keys."""
    env = StreamExecutionEnvironment(
        StreamConfig(batch_size=2, key_capacity=16)
    )
    text = env.add_source(ReplaySource(["4", "0", "2", "0", "8"]))
    h = (
        text.map(lambda l: int(l))
        .filter(lambda v: v != 0)
        .key_by(lambda v: 100 // v)   # would raise on the 0 rows
        .reduce(lambda a, b: a + b)
        .collect()
    )
    env.execute("partial-selector")
    # keys 25, 50, 12 -> rolling sums are just the values
    assert h.items == [4, 2, 8]


def test_later_key_by_supersedes_computed_key():
    """key_by(computed).key_by(0): the LAST key_by wins (Flink
    semantics) — the superseded synthetic column must be dropped, not
    silently kept as the grouping key."""
    assert run(0) == [
        ("a", 1.0), ("b", 10.0), ("a", 3.0), ("b", 30.0), ("a", 7.0),
    ]

    env = StreamExecutionEnvironment(
        StreamConfig(batch_size=2, key_capacity=16)
    )
    text = env.add_source(ReplaySource(LINES))
    h = (
        text.map(parse)
        .key_by(lambda r: 1)          # constant computed key...
        .key_by(0)                    # ...superseded by field 0
        .reduce(lambda a, b: Tuple2(a.f0, a.f1 + b.f1))
        .collect()
    )
    env.execute("superseded")
    assert [(t.f0, t.f1) for t in h.items] == run(0)


def test_computed_selector_checkpoint_resume(tmp_path):
    """Computed-key jobs checkpoint/resume: the restored adaptive
    schema's trailing synthetic column must come back as a
    DerivedKeyTable (intern_values + original-value lookup)."""
    import glob
    import os

    from tpustream.runtime.checkpoint import load_checkpoint

    lines = [f"h{i % 5}{'x' * (i % 3)} {i + 1}" for i in range(12)]

    def job(ckdir=None, restore=None):
        cfg = dict(batch_size=2, key_capacity=16)
        if ckdir:
            cfg.update(checkpoint_dir=ckdir, checkpoint_interval_batches=1)
        env = StreamExecutionEnvironment(StreamConfig(**cfg))
        if restore:
            env.restore_from_checkpoint(restore)
        text = env.add_source(ReplaySource(lines))
        h = (
            text.map(parse)
            .key_by(lambda r: len(r.f0))
            .reduce(lambda a, b: Tuple2(a.f0, a.f1 + b.f1))
            .collect()
        )
        env.execute("computed-ckpt")
        return [(t.f0, t.f1) for t in h.items]

    ckdir = str(tmp_path / "ck")
    full = job(ckdir=ckdir)
    assert full
    snaps = sorted(glob.glob(os.path.join(ckdir, "ckpt-*.npz")))
    assert snaps
    if len(snaps) > 2:
        snaps = [snaps[0], snaps[-1]]
    for snap in snaps:
        ck = load_checkpoint(snap)
        assert job(restore=snap) == full[ck.emitted :]


def test_computed_selector_on_chain_stage():
    """A computed KeySelector on a CHAIN stage: the glue derives the
    key from each hand-off batch. Checked against a record-at-a-time
    Python oracle of the two rolling stages."""
    lines = ["a 1", "bb 10", "c 2", "dd 20", "e 4", "ff 40", "a 8"]

    env = StreamExecutionEnvironment(
        StreamConfig(batch_size=2, key_capacity=16)
    )
    text = env.add_source(ReplaySource(lines))
    h = (
        text.map(parse)
        .key_by(0)
        .reduce(lambda a, b: Tuple2(a.f0, a.f1 + b.f1))
        .key_by(lambda r: len(r.f0))
        .reduce(lambda a, b: Tuple2(a.f0, a.f1 + b.f1))
        .collect()
    )
    env.execute("chained-computed")

    # oracle: stage 1 = per-name rolling sum (one emission per record);
    # stage 2 = rolling sum grouped by len(name), Flink stale-field
    # record semantics (first record's f0 kept per group)
    s1_state, s1_out = {}, []
    for ln in lines:
        k, v = ln.split(" ")[0], float(ln.split(" ")[1])
        s1_state[k] = s1_state.get(k, 0.0) + v
        s1_out.append((k, s1_state[k]))
    s2_state, expect = {}, []
    for k, v in s1_out:
        g = len(k)
        if g in s2_state:
            k0, v0 = s2_state[g]
            s2_state[g] = (k0, v0 + v)
        else:
            s2_state[g] = (k, v)
        expect.append(s2_state[g])
    assert [(t.f0, t.f1) for t in h.items] == expect


def test_computed_selector_on_chain_stage_checkpoint_resume(tmp_path):
    """Chain-stage DerivedKeyTables are runtime-built: a resumed run
    must reload their snapshot (chain_key_tables) so saved state rows
    keep their key ids."""
    import glob
    import os

    from tpustream import (
        BoundedOutOfOrdernessTimestampExtractor,
        Time,
        TimeCharacteristic,
    )
    from tpustream.runtime.checkpoint import load_checkpoint

    class Ts(BoundedOutOfOrdernessTimestampExtractor):
        def __init__(self):
            super().__init__(Time.milliseconds(1000))

        def extract_timestamp(self, value):
            return int(value.split(" ")[0])

    lines = [
        f"{1000 + i * 900} {'k' * (i % 3 + 1)}{i % 4} {i + 1}"
        for i in range(16)
    ] + ["60000 z 100"]

    def job(ckdir=None, restore=None):
        cfg = dict(batch_size=4, key_capacity=16)
        if ckdir:
            cfg.update(checkpoint_dir=ckdir, checkpoint_interval_batches=1)
        env = StreamExecutionEnvironment(StreamConfig(**cfg))
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        if restore:
            env.restore_from_checkpoint(restore)
        text = env.add_source(ReplaySource(lines))
        h = (
            text.assign_timestamps_and_watermarks(Ts())
            .map(lambda l: Tuple2(l.split(" ")[1], int(l.split(" ")[2])))
            .key_by(0)
            .time_window(Time.seconds(5))
            .reduce(lambda a, b: Tuple2(a.f0, a.f1 + b.f1))
            .key_by(lambda r: len(r.f0))
            .time_window(Time.seconds(15))
            .reduce(lambda a, b: Tuple2(a.f0, a.f1 + b.f1))
            .collect()
        )
        env.execute("chained-computed-ckpt")
        return [(t.f0, t.f1) for t in h.items]

    ckdir = str(tmp_path / "ck")
    full = job(ckdir=ckdir)
    assert full
    snaps = sorted(glob.glob(os.path.join(ckdir, "ckpt-*.npz")))
    assert snaps
    if len(snaps) > 2:
        snaps = [snaps[0], snaps[-1]]
    for snap in snaps:
        ck = load_checkpoint(snap)
        assert job(restore=snap) == full[ck.emitted :]
