"""Fetch + emit, paced: per firing step (its ``emit`` span received
sink rows inside the window), from the start of the step's
``dispatch`` span to the end of the last ``fetch`` span that ended
before its ``emit`` began: the device step and the wait for its rows,
in ms, averaged. One ``fetch`` serves a group of steps, so it is found
by time, not by step. A run whose spans carry no step ids (no
``source`` span) reads nothing."""

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _firing_emits():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_fire_device_ms_paced",
        os.path.join(_HERE, "fire_device_ms.paced.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.firing_emits


def read(ctx):
    if not any(k == "source" for k, *_ in ctx.spans):
        return None
    ep = ctx.tracer_epoch
    starts = {s: t0 + ep for k, s, _o, t0, _d in ctx.spans if k == "dispatch"}
    ends = np.sort(np.asarray([t0 + ep + d for k, _s, _o, t0, d in ctx.spans
                               if k == "fetch"], dtype=np.float64))
    got = []
    for step, start, _dur in _firing_emits()(ctx):
        k = int(np.searchsorted(ends, start, side="right")) - 1
        if step in starts and k >= 0 and ends[k] >= starts[step]:
            got.append(ends[k] - starts[step])
    return float(np.mean(got)) * 1e3 if got else None
