"""Device step, rolling aggregate, against the HBM roofline: the step's
compulsory HBM bytes per run over the device time per run of the
jitted step (first chip, the runs that start inside the traced window),
over the chip's HBM peak (``benchmark/peaks.json``), in %.

The compulsory bytes (:func:`step_bytes`) are counted from the stream
and the run's real step boundaries, at the widths the program stores
for the chapter-2 running max (``keyBy(host).max(usage)`` over
``Tuple3(host, cpu, usage)``): each record's columns read once (host
and cpu ids 4 B each, the usage image 8 B), each emitted row's columns
written once (the same 16 B), and for each distinct key of the step its
stored row gathered (the cpu plane and the two image planes, 12 B) and
its tail scattered (the two image planes, 8 B). That is a lower bound
on the traffic, so the share cannot honestly pass 100%; it reads the
same work whatever implements the step."""

import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = os.path.join(os.path.dirname(_HERE), "peaks.json")
STEP = r"^jit_step\b|^jit_step\("
IN_BYTES = 4 + 4 + 8     # host id, cpu id, usage image
OUT_BYTES = 4 + 4 + 8    # the emitted (host, cpu, max image)
GATHER_BYTES = 4 + 8     # a key's stored cpu id and max image
SCATTER_BYTES = 8        # a key's new max image
SAMPLE_STEPS = 8


def step_bytes(rows: int, keys: int) -> int:
    """Compulsory HBM bytes of one step of ``rows`` records over
    ``keys`` distinct keys."""
    return rows * (IN_BYTES + OUT_BYTES) + keys * (GATHER_BYTES
                                                    + SCATTER_BYTES)


def bytes_per_step(stream, step_sizes, steps) -> float:
    """Mean :func:`step_bytes` over up to ``SAMPLE_STEPS`` of ``steps``
    (indices into ``step_sizes``), evenly spaced; 0 without steps."""
    steps = list(steps)
    if not steps:
        return 0.0
    ends = np.cumsum(np.asarray(step_sizes, dtype=np.int64))
    picked = [steps[int(i)] for i in np.unique(np.linspace(
        0, len(steps) - 1, min(SAMPLE_STEPS, len(steps))).astype(int))]
    total = 0
    for s in picked:
        n = int(step_sizes[s])
        hosts = stream.events(int(ends[s]) - n, n)["host"]
        total += step_bytes(n, len(np.unique(hosts)))
    return total / len(picked)


def share(ctx, peak_bytes_per_s: float):
    runs = ctx.trace.module_runs(STEP)
    run = ctx.run
    steps = range(run.s_ws, run.s_we)
    if not runs or not len(steps):
        return None
    step_s = sum(e - s for s, e in runs) / len(runs) / 1e9
    per_step = bytes_per_step(run.stream, run.step_sizes, steps)
    if step_s <= 0 or per_step <= 0:
        return None
    return 100.0 * per_step / step_s / peak_bytes_per_s


def read(ctx):
    if ctx.trace is None:
        return None
    import jax

    with open(PEAKS) as fh:
        table = json.load(fh)["devices"]
    peak = table.get(jax.devices()[0].device_kind)
    if peak is None:
        return None
    return share(ctx, float(peak["hbm_bytes_per_s"]))
