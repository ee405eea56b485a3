"""The rolling step's readers, ``rolling_combine_device_ms``,
``rolling_state_device_ms`` and ``rolling_roofline``, on a hand-built
context: two runs of the step whose scoped ops overlap, and a short
stream whose distinct hosts per step are counted by hand."""

import importlib.util
import os
import types

import numpy as np
import pytest

import harness
import reduce_trace as rt
from conftest import BENCH

MS = 1_000_000  # trace clock: ns


def module(name):
    spec = importlib.util.spec_from_file_location(
        "test_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Stream:
    """Two steps of 8 events: hosts 3 1 3 0 | 2 2 2 5."""

    hosts = np.array([3, 1, 3, 0, 2, 2, 2, 5])

    def events(self, start, n):
        return {"host": self.hosts[start:start + n]}


def trace():
    """Two runs of the step in the window on chip 0 (10 and 20 ms), one
    before it; the sort nests in the scan's fusion."""
    return rt.Trace(
        window=(0, 100 * MS),
        ops={0: [
            ("%sort.1 = s32[8] sort(...)", 11 * MS, 13 * MS),
            ("%fusion.4 = s64[8] fusion(...)", 12 * MS, 15 * MS),
            ("%gather.2 = s32[8] gather(...)", 15 * MS, 16 * MS),
            ("%scatter.7 = s32[64] scatter(...)", 16 * MS, 18 * MS),
            ("%fusion.9 = s32[8] fusion(...)", 18 * MS, 19 * MS),
            ("%sort.1 = s32[8] sort(...)", 51 * MS, 54 * MS),
            ("%scatter.7 = s32[64] scatter(...)", 60 * MS, 61 * MS),
            ("%sort.1 = s32[8] sort(...)", -8 * MS, -7 * MS),
        ]},
        modules={0: [("jit_step(3)", -10 * MS, -4 * MS),
                     ("jit_step(3)", 10 * MS, 20 * MS),
                     ("jit_step(3)", 50 * MS, 70 * MS)]},
        spans={rt.WINDOW: [(0, 100 * MS)]},
    )


SCOPES = {"jit_step": {"sort.1": "combine", "fusion.4": "combine",
                       "gather.2": "state", "scatter.7": "state",
                       "fusion.9": "compact"}}


def ctx(scopes=SCOPES, steps=(0, 2)):
    job_obs = types.SimpleNamespace(op_scopes=scopes)
    env = types.SimpleNamespace(
        metrics=types.SimpleNamespace(job_obs=job_obs))
    run = types.SimpleNamespace(env=env, stream=Stream(),
                                step_sizes=[4, 4], s_ws=steps[0],
                                s_we=steps[1])
    return harness.Context(run=run, trace=trace())


def test_combine_and_state_are_the_union_per_step_run():
    # run 1: combine [11, 15], state [15, 18]; run 2: combine [51, 54],
    # state [60, 61]; the run before the window does not count
    combine = harness.reader("rolling_combine_device_ms")
    state = harness.reader("rolling_state_device_ms")
    assert combine(ctx()) == pytest.approx((4 + 3) / 2)
    assert state(ctx()) == pytest.approx((3 + 1) / 2)
    # a program without the scopes (the parent of these metrics)
    bare = {"jit_step": {"fusion.9": "compact"}}
    assert combine(ctx(scopes=bare)) is None
    assert state(ctx(scopes=bare)) is None
    assert combine(ctx(scopes=None)) is None


def test_step_bytes_by_hand():
    m = module("rolling_roofline")
    # 4 records over 3 hosts: each record read (16 B) and emitted
    # (16 B); each host's stored row gathered (12 B) and its tail
    # scattered (8 B)
    assert m.step_bytes(4, 3) == 4 * 32 + 3 * 20
    # steps 0 and 1 of the stream: 3 and 2 distinct hosts
    assert m.bytes_per_step(Stream(), [4, 4], [0, 1]) == pytest.approx(
        (m.step_bytes(4, 3) + m.step_bytes(4, 2)) / 2)
    assert m.bytes_per_step(Stream(), [4, 4], []) == 0.0


def test_roofline_reads_bytes_over_step_time():
    m = module("rolling_roofline")
    per_step = (m.step_bytes(4, 3) + m.step_bytes(4, 2)) / 2
    step_s = (10 + 20) / 2 / 1e3
    assert m.share(ctx(), 1e6) == pytest.approx(
        100 * per_step / step_s / 1e6)
    # no step in the window reads nothing
    assert m.share(ctx(steps=(1, 1)), 1e6) is None


def test_roofline_takes_the_chips_peak(monkeypatch):
    import jax

    read = harness.reader("rolling_roofline")
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(device_kind="TPU v5 lite")])
    per_step = (4 * 32 + 3 * 20 + 4 * 32 + 2 * 20) / 2
    assert read(ctx()) == pytest.approx(100 * per_step / 0.015 / 819e9)
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(device_kind="cpu")])
    assert read(ctx()) is None
    bare = ctx()
    bare.trace = None
    assert read(bare) is None
