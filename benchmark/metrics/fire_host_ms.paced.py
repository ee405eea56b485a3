"""Pack + H2D, paced: per firing step (its ``emit`` span received sink
rows inside the window), from the end of the step's ``source`` span
(the batch handed over) to the start of its ``dispatch`` span (the
step enqueued): parse, pack, upload staging and the wait for an
in-flight slot, in ms, averaged. Steps are joined by the id every span
carries; a run whose spans carry none (no ``source`` span) reads
nothing."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def _firing_emits():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_fire_device_ms_paced",
        os.path.join(_HERE, "fire_device_ms.paced.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.firing_emits


def read(ctx):
    ends = {s: t0 + d for k, s, _o, t0, d in ctx.spans if k == "source"}
    starts = {s: t0 for k, s, _o, t0, _d in ctx.spans if k == "dispatch"}
    got = [starts[s] - ends[s] for s, _t, _d in _firing_emits()(ctx)
           if s in ends and s in starts]
    return sum(got) / len(got) * 1e3 if got else None
