"""Device step, fire branch: per run of the jitted step that starts
inside the traced window, the device time of the operations the
compiled step maps to its ``fire`` scope (the program's op-to-scope
map, ``JobObs.op_scopes``, against the ``XLA Ops`` events of the first
chip), in ms. TPU op events nest (a conditional covers its branch), so
the time is the union of their intervals. Read it beside
``step_device_ms``."""

import numpy as np
from reduce_trace import union

STEP = "jit_step"
SCOPE = "fire"


def scope_intervals(ctx, scope=SCOPE):
    """``(union of the intervals of the ops mapped to ``scope``, number
    of step runs)`` over the step's runs that start inside the window;
    None when the run carries no op-to-scope map or no step ran."""
    if ctx.trace is None:
        return None
    job_obs = getattr(getattr(getattr(ctx.run, "env", None), "metrics",
                              None), "job_obs", None)
    scopes = (getattr(job_obs, "op_scopes", None) or {}).get(STEP)
    if not scopes:
        return None
    tr = ctx.trace
    devs = sorted(set(tr.ops) | set(tr.modules))
    if not devs:
        return None
    lo, hi = tr.window
    runs = sorted((s, e) for n, s, e in tr.modules.get(devs[0], [])
                  if n.split("(", 1)[0] == STEP and lo <= s < hi)
    if not runs:
        return None
    starts = np.asarray([s for s, _ in runs], np.float64)
    picked = []
    for name, s, e in tr.ops.get(devs[0], []):
        if scopes.get(name.split(" = ", 1)[0].lstrip("%")) != scope:
            continue
        i = int(np.searchsorted(starts, s, side="right")) - 1
        if i >= 0 and s < runs[i][1]:
            picked.append((s, min(e, runs[i][1])))
    if not picked:
        return None
    return union(picked), len(runs)


def read(ctx):
    got = scope_intervals(ctx)
    if got is None:
        return None
    u, n_runs = got
    return float((u[:, 1] - u[:, 0]).sum()) / n_runs / 1e6
