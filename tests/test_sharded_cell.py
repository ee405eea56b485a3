"""The four-chip benchmark cell, ``bw-eventtime-4M-p4.replay``, at a
tiny size on four of the CPU's virtual devices: the benchmark's own cut
(``benchmark/tests/conftest.py`` ``tiny_root``: 2^14 channels, batch
4,096, ~5% low-flow channels) run through ``harness.run_cell`` comes out
correct against the reference, and an exchange too small for its
records (``exchange_capacity_factor``) comes out not correct. On one
fixed stream the job at parallelism 4 emits the rows it emits at
parallelism 1, builds its step as often, and its compiled step maps the
keyBy ``all_to_all`` to the ``exchange`` scope and its all-reduces to
``allreduce``, while the single-chip step's map is what it was."""

import collections
import importlib.util
import os
import re
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "bw-eventtime-4M-p4.replay"
SEED = 2**33 + 25
SECONDS = 1.5
# the scopes a one-chip step opens: naming the mesh's two must leave
# its op-to-scope map as it was
ONE_CHIP_SCOPES = ("unpack", "retarget", "scatter", "fire", "sweep",
                   "compact")


def _tiny_root():
    """``tiny_root`` of the benchmark's tests, which also puts the
    benchmark's modules on the path."""
    spec = importlib.util.spec_from_file_location(
        "bench_tests_conftest", os.path.join(BENCH, "tests", "conftest.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.tiny_root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    tiny_root = _tiny_root()
    import harness

    return harness.Spec(tiny_root(str(tmp_path_factory.mktemp("tiny4"))))


def run_cell(spec, overrides=None):
    import harness

    return harness.run_cell(spec, CELL, SEED, SECONDS, False,
                            time.perf_counter(), overrides=overrides,
                            log=lambda msg: None)


def test_tiny_four_chip_cell_is_correct(tiny):
    assert tiny.cell(CELL)["chips"] == 4
    out = run_cell(tiny)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] >= 4


def test_exchange_that_drops_records_is_not_correct(tiny):
    # 64 rows per destination for ~256 a step: the exchange overflows,
    # the job fails on strict_overflow, and the overflow is counted
    out = run_cell(tiny, {"exchange_capacity_factor": 0.25})
    assert not out["correct"], out["checks"]
    assert out["checks"]["overflow"]["value"] > 0
    assert out["failed"] == out["attempted"]


# ---------------------------------------------------------------------------
# one fixed stream at parallelism 1 and 4
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def both(tiny):
    """The cell's job on the first three minutes of its stream at
    parallelism 1 and 4, obs and the profiler bridge on: rows, the
    op-to-scope map, the compiled step's HLO text and the step builds."""
    import harness
    from compileclock import CompileClock
    from tpustream import StreamExecutionEnvironment
    from tpustream.config import ObsConfig, StreamConfig
    from tpustream.obs import compilation
    from tpustream.runtime.sources import ReplayBytesSource

    conf = tiny.config(tiny.cell(CELL)["config"])
    batch = int(tiny.traffic(CELL)["batch_size"])
    job = harness.job_module(conf["kind"])
    stream = job.Stream(conf, SEED)
    blocks = [(stream.render(i, batch), batch)
              for i in range(0, 3 * stream.per_min, batch)]
    clock = CompileClock()
    texts = []
    scope_map = compilation.scope_map

    def keep_text(text):
        texts.append(text)
        return scope_map(text)

    compilation.scope_map = keep_text
    out = {}
    try:
        for p in (1, 4):
            settings = dict(job.stream_config(conf), parallelism=p,
                            batch_size=batch,
                            obs=ObsConfig(enabled=True, profiler_bridge=True))
            env = StreamExecutionEnvironment(StreamConfig(**settings))
            rows = []
            job.build(env, env.add_source(ReplayBytesSource(blocks))).add_sink(
                lambda t: rows.append((t.f0, t.f1)))
            b0, t0 = len(clock.builds), len(texts)
            res = env.execute(f"p{p}")
            out[p] = {
                "rows": rows,
                "scopes": res.metrics.job_obs.op_scopes["jit_step"],
                "text": texts[t0:][-1],
                "builds": sum(f == "jit(step)" for _, f in clock.builds[b0:]),
            }
    finally:
        compilation.scope_map = scope_map
    return out


def test_four_shards_emit_the_rows_of_one_chip(both):
    assert both[1]["rows"]
    assert collections.Counter(both[4]["rows"]) == collections.Counter(
        both[1]["rows"])


def test_sharded_step_builds_as_often_as_one_chip(both):
    assert both[4]["builds"] == both[1]["builds"] >= 1


_COLLECTIVE = re.compile(r"(?:^|[\s)])(all-to-all|all-reduce)(?:-start)?\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _collectives(text):
    """``{instruction: (opcode, op_name)}`` of the HLO text's
    collectives."""
    out = {}
    for line in text.splitlines():
        lhs, eq, rhs = line.partition(" = ")
        m = _COLLECTIVE.search(rhs) if eq else None
        if m:
            name = _OP_NAME.search(rhs)
            out[lhs.split()[-1].lstrip("%")] = (
                m.group(1), name.group(1) if name else "")
    return out


def test_sharded_scope_map_names_the_exchange_and_allreduce(both):
    scopes, ops = both[4]["scopes"], _collectives(both[4]["text"])
    a2a = [n for n, (op, _) in ops.items() if op == "all-to-all"]
    # the watermark's one-hot psum and the counters' psums; the
    # executor's count of emitted rows over the sharded mask is an
    # all-reduce the partitioner adds outside the program
    psum = [n for n, (op, path) in ops.items()
            if op == "all-reduce"
            and path.rsplit("/", 1)[-1].startswith("psum")]
    assert a2a and psum, ops
    assert {scopes.get(n) for n in a2a} == {"exchange"}
    assert {scopes.get(n) for n in psum} == {"allreduce"}
    # the pane ring's phases keep their names on the mesh
    assert {"scatter", "fire", "retarget"} <= set(scopes.values())


def test_single_chip_scope_map_is_unchanged(both, monkeypatch):
    from tpustream.obs import compilation

    scopes, text = both[1]["scopes"], both[1]["text"]
    assert not {"exchange", "allreduce"} & set(scopes.values())
    assert not _collectives(text)
    monkeypatch.setattr(compilation, "STEP_SCOPES", ONE_CHIP_SCOPES)
    assert compilation.scope_map(text)[1] == scopes
