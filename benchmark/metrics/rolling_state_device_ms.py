"""Device step, rolling aggregate: per run of the jitted step that
starts inside the traced window, the device time of the operations the
compiled step maps to its ``state`` scope (``ops/rolling.py``: the
gathers of the stored rows of the batch's keys and the scatters of the
tails into the word planes), on the first chip, in ms, read as
``rolling_combine_device_ms`` reads ``combine``. A program without the
scope reads nothing."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
SCOPE = "state"


def read(ctx):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_rolling_combine_device_ms",
        os.path.join(_HERE, "rolling_combine_device_ms.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx, SCOPE)
