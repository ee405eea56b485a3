"""The readers of the step-tagged spans and the op-to-scope map, on
hand-built contexts: program spans, sink arrival stamps, and a small
trace whose op events nest (a conditional covers its branch)."""

import types

import pytest

import harness
import reduce_trace as rt

MS = 1_000_000  # trace clock: ns


def trace():
    """Two runs of the step in the window, one before it, and one run
    of another program; the step's fire ops nest."""
    return rt.Trace(
        window=(0, 100 * MS),
        ops={0: [
            ("%conditional.6 = (f32[8]) conditional(...)", 10 * MS, 20 * MS),
            ("%fusion.2 = f32[8] fusion(...)", 12 * MS, 15 * MS),   # inside
            ("%fusion.1 = f32[8] fusion(...)", 20 * MS, 28 * MS),   # scatter
            ("%conditional.6 = (f32[8]) conditional(...)", 90 * MS, 94 * MS),
            ("%conditional.6 = (f32[8]) conditional(...)", 60 * MS, 61 * MS),
            ("%conditional.6 = (f32[8]) conditional(...)", -9 * MS, -5 * MS),
        ]},
        modules={0: [("jit_step(11)", -10 * MS, -4 * MS),
                     ("jit_step(11)", 10 * MS, 30 * MS),
                     ("jit_convert_element_type(3)", 59 * MS, 62 * MS),
                     ("jit_step(11)", 90 * MS, 110 * MS)]},
        spans={rt.WINDOW: [(0, 100 * MS)],
               "tpustream.source": [(0, 10 * MS), (40 * MS, 60 * MS)],
               "tpustream.parse": [(60 * MS, 70 * MS)],
               "tpustream.parse.remap": [(62 * MS, 66 * MS)]},
    )


SCOPES = {"jit_step": {"conditional.6": "fire", "fusion.2": "fire",
                       "fusion.1": "scatter"}}


def ctx(spans=(), times=None, scopes=SCOPES, tr=None):
    job_obs = types.SimpleNamespace(op_scopes=scopes)
    env = types.SimpleNamespace(metrics=types.SimpleNamespace(job_obs=job_obs))
    run = types.SimpleNamespace(env=env, times=times, t_ws=0.5, t_we=5.0)
    return harness.Context(run=run, spans=list(spans), tracer_epoch=0.0,
                           trace=tr if tr is not None else trace())


def test_source_wait_and_remap_shares():
    c = ctx()
    assert harness.reader("source_wait_share")(c) == pytest.approx(0.30)
    assert harness.reader("remap_share")(c) == pytest.approx(0.04)
    # a program without these spans reads nothing
    bare = trace()
    bare.spans = {rt.WINDOW: bare.spans[rt.WINDOW]}
    for name in ("source_wait_share", "remap_share"):
        assert harness.reader(name)(ctx(tr=bare)) is None


def test_fire_device_ms_is_the_union_per_step_run():
    # run 1: conditional [10, 20] covers fusion.2; run 2: [90, 94];
    # the same name in another program and the run before the window
    # do not count
    read = harness.reader("fire_device_ms")
    assert read(ctx()) == pytest.approx((10 + 4) / 2)
    assert read(ctx(scopes=None)) is None          # no op-to-scope map
    assert read(ctx(scopes={"jit_step": {"fusion.1": "scatter"}})) is None


# a fetch serving a group of two steps (5 and 6), then one serving 7;
# rows reach the sink inside the emit of step 6 and of step 7
PACED = [
    ("source", 5, "", 1.000, 0.010), ("dispatch", 5, "w", 1.020, 0.002),
    ("source", 6, "", 1.100, 0.005), ("dispatch", 6, "w", 1.110, 0.002),
    ("fetch", 6, "w", 1.150, 0.030),
    ("emit", 5, "w", 1.181, 0.001), ("emit", 6, "w", 1.183, 0.020),
    ("source", 7, "", 1.300, 0.004), ("dispatch", 7, "w", 1.320, 0.002),
    ("fetch", 7, "w", 1.350, 0.010), ("emit", 7, "w", 1.361, 0.010),
]
TIMES = [1.190, 1.195, 1.365]


def test_fire_host_ms_paced_joins_source_and_dispatch_by_step():
    read = harness.reader("fire_host_ms.paced")
    # step 6: source ends 1.105, dispatch starts 1.110; step 7: 1.304
    # to 1.320
    assert read(ctx(PACED, TIMES)) == pytest.approx((5 + 16) / 2)
    assert read(ctx(PACED, [4.9])) is None          # no firing step


def test_fire_fetch_ms_paced_takes_the_group_fetch_by_time():
    read = harness.reader("fire_fetch_ms.paced")
    # step 6: dispatch 1.110 -> its group's fetch ends 1.180; step 7:
    # 1.320 -> 1.360
    assert read(ctx(PACED, TIMES)) == pytest.approx((70 + 40) / 2)
    # spans without step ids (no source span) read nothing
    old = [s for s in PACED if s[0] != "source"]
    assert read(ctx(old, TIMES)) is None


def test_fire_device_ms_paced_divides_by_firing_steps():
    read = harness.reader("fire_device_ms.paced")
    # 14 ms of fire over the window, two steps delivered rows
    assert read(ctx(PACED, TIMES)) == pytest.approx(14 / 2)
    assert read(ctx(PACED, [])) is None
    assert read(ctx(PACED, TIMES, scopes={})) is None
