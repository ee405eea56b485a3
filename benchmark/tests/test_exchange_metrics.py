"""The keyBy exchange's readers, ``exchange_device_ms`` and
``exchange_ici_share``, on a hand-built context: two runs of the
sharded step whose exchange ops overlap, and a 16-event stream whose
off-chip payload is counted by hand."""

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
    """16 events over 8 channels. First appearances intern them as
    5->0 3->1 0->2 7->3 1->4 2->5 6->6 4->7."""

    n_keys = 8
    keys = np.array([5, 3, 5, 0, 7, 3, 1, 2,
                     6, 4, 0, 5, 3, 7, 2, 6])

    def events(self, start, n):
        return {"key": self.keys[start:start + n]}


def trace():
    """Two runs of the step in the window on chip 0, one before it;
    chip 1 runs an all_to_all too (not read)."""
    return rt.Trace(
        window=(0, 100 * MS),
        ops={0: [
            ("%sort.3 = s32[8] sort(...)", 11 * MS, 12 * MS),
            ("%all_to_all.48 = u32[4,1,2] all-to-all(...)", 12 * MS, 13 * MS),
            ("%all_to_all.50 = u32[4,1,2] all-to-all(...)",
             int(12.5 * MS), int(14.5 * MS)),
            ("%fusion.9 = s32[8] fusion(...)", 15 * MS, 25 * MS),
            ("%all-reduce.2 = s64[] all-reduce(...)", 26 * MS, 27 * MS),
            ("%sort.3 = s32[8] sort(...)", 51 * MS, 52 * MS),
            ("%all_to_all.48 = u32[4,1,2] all-to-all(...)", 52 * MS, 53 * MS),
            ("%all_to_all.48 = u32[4,1,2] all-to-all(...)", -8 * MS, -7 * MS),
        ], 1: [
            ("%all_to_all.48 = u32[4,1,2] all-to-all(...)", 12 * MS, 20 * MS),
        ]},
        modules={0: [("jit_step(7)", -10 * MS, -4 * MS),
                     ("jit_step(7)", 10 * MS, 30 * MS),
                     ("jit_step(7)", 50 * MS, 70 * MS)],
                 1: [("jit_step(7)", 10 * MS, 30 * MS)]},
        spans={rt.WINDOW: [(0, 100 * MS)]},
    )


SCOPES = {"jit_step": {"sort.3": "exchange", "all_to_all.48": "exchange",
                       "all_to_all.50": "exchange", "fusion.9": "scatter",
                       "all-reduce.2": "allreduce"}}


def ctx(scopes=SCOPES, steps=(0, 2)):
    job_obs = types.SimpleNamespace(op_scopes=scopes)
    env = types.SimpleNamespace(
        metrics=types.SimpleNamespace(job_obs=job_obs),
        config=types.SimpleNamespace(batch_size=8, parallelism=4))
    run = types.SimpleNamespace(env=env, stream=Stream(),
                                step_sizes=[8, 8], s_ws=steps[0],
                                s_we=steps[1])
    return harness.Context(run=run, trace=trace())


def test_offchip_bytes_counted_by_hand():
    m = module("exchange_ici_share")
    s = Stream()
    assert m.key_ids(s, 16).tolist() == [2, 4, 5, 1, 7, 0, 6, 3]
    # shard 0 holds rows 0-1 of each step: keys 5, 3 (ids 0, 1: one
    # leaves) and 6, 4 (ids 6, 7: both leave)
    assert m.offchip_bytes(s, [8, 8], [0, 1], 8, 4) == 3 * 20
    assert m.offchip_bytes(s, [8, 8], [1], 8, 4) == 2 * 20
    # shard 1 holds rows 2-3: keys 5, 0 (ids 0, 2) and 0, 5: all leave
    assert m.offchip_bytes(s, [8, 8], [0, 1], 8, 4, shard=1) == 4 * 20
    # a short last step places nothing on shard 3
    assert m.offchip_bytes(s, [8, 6], [1], 8, 4, shard=3) == 0
    assert m.offchip_bytes(s, [8, 8], [], 8, 4) == 0


def test_exchange_device_ms_is_the_union_per_step_run():
    # run 1: sort [11, 12] + the two overlapping all_to_alls [12, 14.5];
    # run 2: [51, 53]; the run before the window and chip 1 do not count
    read = harness.reader("exchange_device_ms")
    assert read(ctx()) == pytest.approx((3.5 + 2) / 2)
    assert read(ctx(scopes={"jit_step": {"fusion.9": "scatter"}})) is None
    assert read(ctx(scopes=None)) is None


def test_exchange_ici_share_reads_payload_over_all_to_all_time():
    m = module("exchange_ici_share")
    # all_to_all time per run: ([12, 14.5] + [52, 53]) / 2 = 1.75 ms;
    # payload per step: 60 B / 2 steps
    assert m.share(ctx(), 1e4) == pytest.approx(30 / 1.75e-3 / 1e4)
    # the sort is exchange work but not the all_to_all
    only_sort = {"jit_step": {"sort.3": "exchange"}}
    assert m.share(ctx(scopes=only_sort), 1e4) is None
    # a program without the scope (the parent of this metric) reads
    # nothing, and neither does a window without steps
    assert m.share(ctx(scopes={"jit_step": {}}), 1e4) is None
    assert m.share(ctx(steps=(1, 1)), 1e4) is None


def test_exchange_ici_share_takes_the_chips_peak(monkeypatch):
    import jax

    read = harness.reader("exchange_ici_share")
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(device_kind="TPU v5 lite")])
    assert read(ctx()) == pytest.approx(30 / 1.75e-3 / 200e9)
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(device_kind="cpu")])
    assert read(ctx()) is None
    bare = ctx()
    bare.trace = None
    assert read(bare) is None
