"""Device step, fire branch, paced: the device time of the operations
mapped to the step's ``fire`` scope over the traced window (as
``fire_device_ms`` takes it), divided by the firing steps in the
window, in ms. A firing step is one whose ``emit`` span received sink
rows (the sink's arrival stamps fall inside it; one clock)."""

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _scope_intervals():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_fire_device_ms", os.path.join(_HERE, "fire_device_ms.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_intervals


def firing_emits(ctx):
    """``(step, start, dur)`` of the ``emit`` spans starting inside the
    window inside which the sink received rows."""
    run = ctx.run
    if not run.times or not ctx.spans:
        return []
    arrived = np.sort(np.asarray(run.times, dtype=np.float64))
    ep = ctx.tracer_epoch
    out = []
    for kind, step, _op, t0, dur in ctx.spans:
        start = t0 + ep
        if kind != "emit" or not run.t_ws <= start < run.t_we:
            continue
        lo, hi = np.searchsorted(arrived, [start, start + dur])
        if hi > lo:
            out.append((step, start, dur))
    return out


def read(ctx):
    got = _scope_intervals()(ctx)
    fires = firing_emits(ctx)
    if got is None or not fires:
        return None
    u, _ = got
    return float((u[:, 1] - u[:, 0]).sum()) / len(fires) / 1e6
