"""Native C++ parser: equivalence with the python path + throughput sanity."""

import numpy as np
import pytest

from tpustream import native
from tpustream.hostparse import PlanEvaluator, trace_host_map, trace_timestamp_extractor
from tpustream.records import STR, StringTable
from tpustream.utils.timeutil import iso_local_to_epoch_sec


pytestmark = pytest.mark.skipif(
    not native.available(), reason="native parser failed to build"
)


def make_eval(fn, force_python=False):
    plan = trace_host_map(fn)
    assert plan.fallback_fn is None
    tables = [StringTable() if k == STR else None for k in plan.kinds]
    ev = PlanEvaluator(plan.outputs, tables)
    if force_python:
        ev._native = None
    return ev, tables


def test_native_matches_python_ch1():
    from tpustream.jobs.chapter1_threshold import parse

    lines = [f"15634520{i%60:02d} 10.8.22.{i%7} cpu{i%4} {i%100}.5" for i in range(1000)]
    ev_n, _ = make_eval(parse)
    ev_p, _ = make_eval(parse, force_python=True)
    assert ev_n._native is not None
    cn = ev_n(lines)
    cp = ev_p(lines)
    # string ids were interned into different tables; compare via strings
    tn, tp = ev_n.tables[0], ev_p.tables[0]
    assert [tn.lookup(i) for i in cn[0]] == [tp.lookup(i) for i in cp[0]]
    np.testing.assert_array_equal(cn[2], cp[2])


def test_native_iso_and_arith():
    from tpustream.jobs.chapter3_bandwidth_eventtime import (
        IsoTimestampExtractor,
        parse,
    )
    from tpustream import Time

    lines = [
        f"2019-08-28T{h:02d}:{m:02d}:{s:02d} www.ch{m%5}.com {100+s}"
        for h in (0, 9, 23)
        for m in (0, 30, 59)
        for s in (0, 1, 59)
    ]
    ev_n, _ = make_eval(parse)
    assert ev_n._native is not None
    cols = ev_n(lines)
    expect_ts = [iso_local_to_epoch_sec(l.split(" ")[0]) for l in lines]
    np.testing.assert_array_equal(cols[0], expect_ts)
    np.testing.assert_array_equal(cols[2], [int(l.split(" ")[2]) for l in lines])

    # timestamp extractor plan (epoch ms) through the same machinery
    ex = IsoTimestampExtractor(Time.minutes(1))
    expr = trace_timestamp_extractor(ex.extract_timestamp)
    ev = PlanEvaluator([expr], [None])
    assert ev._native is not None
    (ts_ms,) = ev(lines)
    np.testing.assert_array_equal(ts_ms, np.asarray(expect_ts) * 1000)


def test_native_id_namespace_shared_with_python_interning():
    from tpustream.jobs.chapter1_threshold import parse

    ev, tables = make_eval(parse)
    assert ev._native is not None
    # pre-intern a literal python-side (as a device chain comparison would)
    tables[0].intern("10.8.22.9")
    cols = ev(["1 10.8.22.9 cpu0 1.0", "2 10.8.22.1 cpu1 2.0"])
    assert tables[0].lookup(int(cols[0][0])) == "10.8.22.9"
    assert int(cols[0][0]) == 0  # remapped onto the existing python id


_LITERALS = ["lit-a", "k3", "lit-b"]


@pytest.mark.parametrize(
    "batches",
    [
        # new keys in the middle batch, then only seen keys
        [[f"k{i}" for i in range(20)],
         [k for i in range(10) for k in (f"k{i}", f"n{i}")],
         [f"k{i}" for i in range(5, 16)]],
        # every batch after the first holds seen keys only
        [[f"k{i}" for i in range(50)],
         [f"k{i}" for i in reversed(range(50))],
         [f"k{(7 * i) % 50}" for i in range(50)]],
        # the remap outgrows its first buffer after columns went out
        [[f"k{i}" for i in range(10)],
         [k for i in range(1000) for k in (f"g{i}", f"k{i % 10}")],
         [f"k{i}" for i in range(10)]],
        # keys the python side interned first, among new ones
        [["k3", "lit-b", "x1", "x2"], ["x2", "k3"], ["lit-a", "y", "x1"]],
    ],
    ids=["new_keys_mid", "seen_only", "growth", "literals_first"],
)
def test_native_remap_grows_in_place(batches):
    """The native table's remap onto the python ids is extended by the
    new ids only and gathered through a view of its buffer: per batch,
    the key column holds the StringTable ids the plain first-appearance
    rule gives (literals interned python-side first shift them), a
    batch of seen keys leaves the remap's buffer where it was (no copy
    of all ids), columns returned earlier keep their values after the
    buffer grows, and the counter counts exactly the new native ids."""
    from tpustream.obs.registry import MetricsRegistry

    table = StringTable()
    for s in _LITERALS:
        table.intern(s)
    new_ids = MetricsRegistry().group().counter("intern_new_ids_total")
    p = native.NativeParser(
        " ", [(1, native.KIND_STR, 0), (2, native.KIND_I64, 0)], [table, None]
    )
    (nt,) = [t for t in p.tables if t is not None]
    want_ids = {s: i for i, s in enumerate(_LITERALS)}
    native_seen: set = set()
    returned = []
    for keys in batches:
        data = "\n".join(f"{i} {k} {3 * i}" for i, k in enumerate(keys))
        before = nt.sync()
        counted = new_ids.value
        (ids, vals), bad = p.parse(data.encode(), len(keys), threads=1,
                                   new_ids=new_ids)
        after = nt.sync()
        fresh = [k for k in dict.fromkeys(keys) if k not in native_seen]
        native_seen.update(fresh)
        for k in keys:
            want_ids.setdefault(k, len(want_ids))
        want = np.array([want_ids[k] for k in keys], dtype=np.int32)
        assert bad == 0
        assert ids.dtype == np.int32 and np.array_equal(ids, want)
        assert table.lookup_many(ids) == keys
        assert np.array_equal(vals, 3 * np.arange(len(keys)))
        assert new_ids.value - counted == len(fresh)
        if not fresh:
            assert np.shares_memory(before, after)
        returned.append((ids, want))
        for got, w in returned:
            assert np.array_equal(got, w)
    assert len(nt) == len(native_seen) == new_ids.value


def test_native_parser_throughput():
    from tpustream.jobs.chapter1_threshold import parse

    lines = [
        f"1563452056 10.8.22.{i%250} cpu{i%16} {(i*7)%100}.5" for i in range(200_000)
    ]
    data = "\n".join(lines).encode()
    ev, _ = make_eval(parse)
    assert ev._native is not None
    import time

    t0 = time.perf_counter()
    out = ev.parse_bytes(data, len(lines))
    dt = time.perf_counter() - t0
    rate = len(lines) / dt
    assert out is not None and len(out[0]) == len(lines)
    # sanity: well over a million lines/sec on any modern core
    assert rate > 1e6, f"native parse too slow: {rate:.0f} lines/s"


def test_multithreaded_parse_identical_to_serial():
    """tsp_parse_mt must reproduce the serial kernel EXACTLY, including
    the first-seen intern-id order (chunk order == stream order)."""
    import numpy as np

    from tpustream.hostparse import PlanEvaluator, trace_host_map
    from tpustream.jobs.chapter3_bandwidth_eventtime import parse
    from tpustream.records import STR, StringTable

    lines = [
        f"2019-08-28T10:{(j // 60) % 60:02d}:{j % 60:02d} "
        f"www.ch{(j * 7) % 199}.com {100 + j % 97}"
        for j in range(60_000)
    ]
    data = ("\n".join(lines) + "\n").encode()

    def run(threads):
        plan = trace_host_map(parse)
        tables = [StringTable() if k == STR else None for k in plan.kinds]
        ev = PlanEvaluator(plan.outputs, tables)
        if ev._native is None:
            pytest.skip("native parser unavailable")
        cols, bad = ev._native.parse(data, len(lines), threads=threads)
        tbl = [t for t in ev._native.tables if t is not None][0]
        return [np.asarray(c) for c in cols], bad, list(tbl.py_table._to_str)

    cols1, bad1, strs1 = run(1)
    cols4, bad4, strs4 = run(4)
    assert bad1 == bad4 == 0
    assert strs1 == strs4
    for c1, c4 in zip(cols1, cols4):
        assert np.array_equal(c1, c4)



@pytest.mark.slow
def test_asan_flavor_parses_clean():
    """Build the Makefile's `asan` flavor of the parse kernel and run a
    mixed workload (all kinds, malformed rows, serial + multi-threaded)
    under LD_PRELOADed libasan: any heap overflow / UB in fastparse.cpp
    aborts the subprocess with a sanitizer report."""
    import os
    import subprocess
    import sys

    here = os.path.dirname(native.__file__)
    probe = subprocess.run(
        ["gcc", "-print-file-name=libasan.so"], capture_output=True, text=True
    )
    libasan = probe.stdout.strip()
    if probe.returncode != 0 or not os.path.isabs(libasan):
        pytest.skip("toolchain has no libasan")
    build = subprocess.run(
        ["make", "-C", here, "asan"], capture_output=True, text=True
    )
    if build.returncode != 0:
        pytest.skip(f"asan build unavailable: {build.stderr[-200:]}")

    script = """
import numpy as np
from tpustream import native
from tpustream.records import StringTable

assert native.build_flavor() == "asan", native.build_flavor()
assert native.available(), native.build_error()
specs = [
    (1, native.KIND_STR, 0),
    (2, native.KIND_STR, 0),
    (3, native.KIND_F64, 0),
    (0, native.KIND_I64, 0),
]
p = native.NativeParser(" ", specs, [StringTable(), StringTable(), None, None])
lines = [
    f"15634520{i % 60:02d} 10.8.22.{i % 250} cpu{i % 16} {(i * 7) % 100}.5"
    for i in range(50_000)
]
lines[777] = "garbage"
lines[778] = "1 2"
lines[779] = ""
data = ("\\n".join(lines) + "\\n").encode()
serial, bad1 = p.parse(data, len(lines), threads=1)
p2 = native.NativeParser(" ", specs, [StringTable(), StringTable(), None, None])
mt, bad4 = p2.parse(data, len(lines), threads=4)
assert bad1 == bad4
for a, b in zip(serial, mt):
    assert np.array_equal(a, b)
print("ASAN_PARSE_OK", len(serial[0]), bad1)
"""
    env = dict(os.environ)
    env.update(
        LD_PRELOAD=libasan,
        ASAN_OPTIONS="detect_leaks=0,abort_on_error=1",
        TPUSTREAM_NATIVE_FLAVOR="asan",
        JAX_PLATFORMS="cpu",
    )
    run = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )
    out = run.stdout + run.stderr
    assert run.returncode == 0, out[-2000:]
    assert "ASAN_PARSE_OK" in run.stdout, out[-2000:]
    assert "AddressSanitizer" not in out, out[-2000:]
    assert "runtime error" not in out, out[-2000:]
