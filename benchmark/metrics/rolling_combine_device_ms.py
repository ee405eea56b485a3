"""Device step, rolling aggregate: per run of the jitted step that
starts inside the traced window, the device time of the operations the
compiled step maps to its ``combine`` scope (``ops/rolling.py``: the
sort by key, the segmented scan of the aggregated column and the
compare with the stored values), on the first chip, in ms: the union
of their intervals, as ``fire_device_ms`` takes the ``fire`` scope. A
program without the scope (a build before it was named) reads
nothing."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
SCOPE = "combine"


def _scope_intervals():
    spec = importlib.util.spec_from_file_location(
        "bench_metric_fire_device_ms", os.path.join(_HERE, "fire_device_ms.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.scope_intervals


def read(ctx, scope=SCOPE):
    got = _scope_intervals()(ctx, scope)
    if got is None:
        return None
    u, n_runs = got
    return float((u[:, 1] - u[:, 0]).sum()) / n_runs / 1e6
